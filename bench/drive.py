"""Closed-loop clients: the traffic of a cell, made from its mix file and the
seed, sent through the system under test.

A mix file (``bench/traffic/<mix>.json``) names its ``kind``:

* ``rooms``: a fixed amount of work, ``round(seconds x rate_per_s)``
  distinct rooms drawn in set-up, sent with ``outstanding`` requests in
  flight: each finished request is replaced at once by the next room until
  all are sent, and the window closes when the last one has finished. The
  sizes are the lognormal's stratified quantiles (``voxels``), which the
  seed only reorders, so that every seed offers the same work. A window
  cut at a time instead would hold 15 rooms in one run and 16 in the next,
  as rooms finish in pairs (a wave) seconds apart.
* ``stream``: ``streams`` fixed sensors, each with one frame in flight (a
  sensor drops frames that would queue behind the one being served),
  sending until ``seconds`` have passed.

Both pin the program's plan spec from rooms of the mix's top size drawn
from fixed seeds, and warm up on fixed-seed requests, so that every run of a
config compiles and loads the same programs.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

import jax
import numpy as np

import scenes
import sut

#: seed spaces of the generated rooms: pinning and warm-up rooms are fixed,
#: the window's come from the run's seed
PIN_SEED, WARM_SEED = 7_000_000_001, 7_000_000_002
ROOMS, STREAMS, SAMPLE = 5, 6, 8


def _room(seed_seq, target: int, n_objects: int, cfg: dict):
    return scenes.room_with_voxels(seed_seq, target, n_objects,
                                   cfg["full_scale"], cfg["capacity"])


@dataclass
class Record:
    """One request of the window, timed on the benchmark's own clock."""

    ordinal: int
    n_active: int
    t_submit: float
    t_done: float = math.nan
    status: str = "queued"
    key: tuple = ()            # how to regenerate its input
    logits: np.ndarray | None = None   # kept for the check sample only
    plan_mode: str | None = None


@dataclass
class Window:
    records: list = field(default_factory=list)
    t0: float = 0.0
    t1: float = 0.0
    waves: list = field(default_factory=list)
    stream_counts: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def pin_rooms(mix: dict, cfg: dict) -> list:
    p = mix["pin"]
    rooms = [_room([PIN_SEED, i], p["voxels"], p["objects"], cfg)
             for i in range(p["rooms"])]
    return [sut.scene(c, f, m) for c, f, _, m in rooms]


class _Client:
    """Shared closed-loop bookkeeping: submit, wait on the oldest request,
    sweep the others, keep the logits that the check will need."""

    def __init__(self, eng, keep: set | None):
        self.eng = eng
        self.keep = keep   # ordinals whose logits to keep; None: all
        self.inflight: list = []   # (handle, record)

    def submit(self, req, rec: Record):
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.t_submit = time.perf_counter()
            h = self.eng.submit(req)
        self.inflight.append((h, rec))

    def wait_some(self, timeout_s: float) -> list:
        """Block until the oldest request ends; return every ended one."""
        h, _ = self.inflight[0]
        with jax.profiler.TraceAnnotation("bench.wait"):
            try:
                h.result(timeout=timeout_s)
            except Exception:  # noqa: BLE001 - a failed request is counted
                pass
        now = time.perf_counter()
        ended, still = [], []
        for h, rec in self.inflight:
            if h.done():
                rec.t_done = now
                rec.status = h.status
                r = h.request
                if r.logits is not None and (self.keep is None
                                             or rec.ordinal in self.keep):
                    rec.logits = np.asarray(r.logits)
                info = getattr(r, "plan_info", None)
                if info:
                    rec.plan_mode = info.get("mode")
                r.logits = r.pred = None  # the client keeps what it needs
                ended.append(rec)
            else:
                still.append((h, rec))
        if not ended:
            raise TimeoutError(f"no request ended in {timeout_s} s")
        self.inflight = still
        return ended


class RoomsTraffic:
    def __init__(self, mix: dict, seed: int, seconds: float, cfg: dict):
        self.mix, self.seed = mix, seed
        n = max(mix["outstanding"], round(mix["rate_per_s"] * seconds))
        v = mix["voxels"]
        sizes, objs = scenes.room_sizes(n, seed, v["median"], v["sigma"],
                                        v["lo"], v["hi"], *mix["objects"])
        self.pool = [_room([seed, ROOMS, i], int(sizes[i]), int(objs[i]),
                           cfg) for i in range(n)]
        # small warm-up rooms: the wave program's shapes do not depend on
        # the room, and a small room plans sooner
        self.warm = [_room([WARM_SEED, i], int(mix["warm_voxels"]),
                           mix["objects"][0], cfg) for i in range(3)]
        rng = np.random.default_rng([seed, SAMPLE])
        self.sample = set(rng.choice(n, size=min(mix["check"]["sample"], n),
                                     replace=False).tolist())
        self.largest = int(np.argmax([r[3].sum() for r in self.pool]))

    def scene_of(self, rec: Record):
        c, f, _, m = self.pool[rec.key[0]]
        return c, f, m

    def warm_up(self, eng) -> None:
        """A wave of one room (padded to the batch), then a full one: the
        two kinds of wave the closed loop sends."""
        reqs = [sut.SceneRequest(-1 - i, sut.scene(c, f, m))
                for i, (c, f, _, m) in enumerate(self.warm)]
        for wave in (reqs[:1], reqs[1:]):
            for h in eng.submit(wave):
                h.result(timeout=1200)

    def release(self) -> None:
        """Nothing of the program's state is held between requests."""

    def check_ordinals(self, win: Window) -> list[int]:
        """The sampled rooms and the largest one (every room of the pool is
        sent; one that did not finish has no logits and fails the check)."""
        return sorted(self.sample | {self.largest})

    def run(self, eng, seconds: float) -> Window:
        win = Window()
        cl = _Client(eng, self.sample | {self.largest})
        nxt = 0

        def send():
            nonlocal nxt
            c, f, _, m = self.pool[nxt]
            rec = Record(nxt, int(m.sum()), 0.0, key=(nxt,))
            cl.submit(sut.SceneRequest(nxt, sut.scene(c, f, m)), rec)
            win.records.append(rec)
            nxt += 1

        win.t0 = time.perf_counter()
        for _ in range(self.mix["outstanding"]):
            send()
        while cl.inflight:
            for _ in cl.wait_some(600):
                if nxt < len(self.pool):
                    send()
        win.t1 = max(r.t_done for r in win.records)
        return win


class StreamTraffic:
    def __init__(self, mix: dict, seed: int, seconds: float, cfg: dict):
        self.mix, self.seed = mix, seed
        self.sensors = [
            scenes.FixedSensorStream(
                [seed, STREAMS, s], cfg["full_scale"], cfg["capacity"],
                room_voxels=mix["room_voxels"], n_objects=mix["objects"],
                object_voxels=mix["object_voxels"], dropout=mix["dropout"])
            for s in range(mix["streams"])]
        self.handles = []
        self.next_frame = [0] * len(self.sensors)
        rng = np.random.default_rng([seed, SAMPLE])
        # frames to check: each stream's last frame of the window, and
        # window frames at positions drawn from the seed among each
        # stream's first four (patched plans, but for a rare rebuild)
        self.sample = {(s, int(t)) for s in range(len(self.sensors))
                       for t in rng.choice(4, size=max(
                           1, mix["check"]["sample"] // len(self.sensors)),
                           replace=False)}

    def release(self) -> None:
        """Drop the open streams' cached plans and device buffers."""
        self.handles = []

    def check_ordinals(self, win: Window) -> list[int]:
        """The sampled frames and each stream's last, among those
        finished (the run kept the logits of just these)."""
        return [r.ordinal for r in win.records
                if r.status == "completed" and r.logits is not None]

    def scene_of(self, rec: Record):
        s, t = rec.key
        return self.sensors[s].frame(t)

    def warm_up(self, eng) -> None:
        self.handles = [eng.open_stream(f"sensor{s}",
                                        min_overlap=self.mix["min_overlap"])
                        for s in range(len(self.sensors))]
        # the first sensor's first frame alone (a padded wave), then every
        # sensor's frames together: rebuilt and patched plans, both waves
        self._submit(0).result(timeout=1200)
        while min(self.next_frame) < self.mix["warmup_frames"]:
            hs = [self._submit(s) for s in range(len(self.sensors))
                  if self.next_frame[s] < self.mix["warmup_frames"]]
            for h in hs:
                h.result(timeout=1200)

    def _submit(self, s: int):
        t = self.next_frame[s]
        self.next_frame[s] += 1
        return self.handles[s].submit(sut.scene(*self.sensors[s].frame(t)))

    def run(self, eng, seconds: float) -> Window:
        win = Window()
        t_first = list(self.next_frame)
        cl = _Client(eng, None)
        latest: dict = {}   # stream -> its newest finished record

        def send(s):
            t = self.next_frame[s]
            self.next_frame[s] += 1
            c, f, m = self.sensors[s].frame(t)
            rec = Record(len(win.records), int(m.sum()), 0.0, key=(s, t))
            with jax.profiler.TraceAnnotation("bench.submit"):
                rec.t_submit = time.perf_counter()
                h = self.handles[s].submit(sut.scene(c, f, m))
            cl.inflight.append((h, rec))
            win.records.append(rec)

        before = [h.stats() for h in self.handles]
        win.t0 = time.perf_counter()
        for s in range(len(self.sensors)):
            send(s)
        while cl.inflight:
            for rec in cl.wait_some(600):
                s, t = rec.key
                old = latest.get(s)
                if old is not None and (s, old.key[1] - t_first[s]) \
                        not in self.sample:
                    old.logits = None
                latest[s] = rec
                if time.perf_counter() - win.t0 < seconds:
                    send(s)
        win.t1 = max(r.t_done for r in win.records)
        after = [h.stats() for h in self.handles]
        win.stream_counts = {
            k: sum(a[k] - b[k] for a, b in zip(after, before))
            for k in ("frames", "reused", "patched", "rebuilt")}
        return win


KINDS = {"rooms": RoomsTraffic, "stream": StreamTraffic}


def traffic(mix: dict, seed: int, seconds: float, cfg: dict):
    """The mix's traffic on the grid and capacity of the configuration
    (``full_scale``, ``capacity``)."""
    return KINDS[mix["kind"]](mix, seed, seconds, cfg)
