"""Continuous batching + SLO admission vs single-signature FIFO serving.

Measures the ROADMAP "continuous batching" item under heavy mixed traffic:
a bursty-Poisson arrival process with a diurnal rate ramp submits scenes of
mixed sizes (mostly small scans, some large) from two tenants (a low-
priority "free" flood and a weighted, deadline-carrying "paid" tenant) into
two serving arms over identical request content:

* **fifo** — the pre-redesign baseline: one pinned signature at the max
  capacity, FIFO waves, no admission policy. Every 150-voxel scan pays a
  full-capacity wave, and the burst backlog head-of-line blocks everyone.
* **bucketed** — a two-tier ``SignatureFamily`` (small scans serve from the
  small-capacity signature) plus an ``AdmissionPolicy``: priority/deadline
  ordering, weighted tenant fairness, backpressure, and deadline shedding.

Each arm is driven tick-by-tick (``submit(group)`` + ``serve(max_waves=1)``
per tick, then a full drain) so queue backlog builds exactly as the arrival
process dictates. Rows report per-arm p50/p99 end-to-end latency, deadline
goodput, shed counts and compile counts; the headline row derives the
bucketed-over-fifo p99 speedup and goodput delta.

Standalone CLI (what the CI smoke job runs):

    python -m benchmarks.bench_admission --quick --json BENCH_admission.json
"""
from __future__ import annotations

import math

import jax
import numpy as np

from benchmarks.common import emit, standalone_bench_main
from repro import engine
from repro.data.scenes import N_CLASSES, make_scene
from repro.models.scn import UNetConfig, init_unet
from repro.serving import AdmissionPolicy
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.sparse.tensor import SparseVoxelTensor

RES, CAP, SMALL_CAP = 16, 1024, 256


def _scene_with(seed: int, n_active: int) -> SparseVoxelTensor:
    """A CAP-capacity scene trimmed to exactly ``n_active`` active voxels
    (the client over-pads; bucketing works off active counts)."""
    coords, feats, _, mask = make_scene(seed, resolution=RES, capacity=CAP)
    mask = np.asarray(mask).copy()
    idx = np.flatnonzero(mask)
    n_active = min(n_active, len(idx))
    mask[idx[n_active:]] = False
    return SparseVoxelTensor(np.asarray(coords), np.asarray(feats), mask)


def _traffic(rng, n_ticks: int, base_rate: float, deadlines: dict):
    """Per-tick request groups: bursty Poisson counts whose rate follows a
    diurnal ramp (quiet -> 3x peak mid-run -> quiet), mixed sizes/tenants.

    Returns ``[(tenant, priority, deadline_ms, scene), ...]`` per tick —
    request *content* only, so each serving arm gets its own fresh
    ``SceneRequest`` objects over identical scenes.
    """
    groups = []
    seed = 0
    for t in range(n_ticks):
        diurnal = 1.0 + 2.0 * math.sin(math.pi * t / max(n_ticks - 1, 1))
        group = []
        for _ in range(rng.poisson(base_rate * diurnal)):
            seed += 1
            small = rng.random() < 0.75  # traffic is mostly small scans
            paid = rng.random() < 0.30
            n_active = int(rng.integers(100, 220) if small
                           else rng.integers(400, 600))
            group.append((
                "paid" if paid else "free",
                1 if paid else 0,
                deadlines["paid" if paid else "free"],
                _scene_with(seed, n_active),
            ))
        groups.append(group)
    return groups


def _drive(eng, groups):
    """Tick-driven serve: submit each tick's arrivals, admit one wave per
    tick (backlog builds through the ramp), then drain the remainder."""
    handles = []
    for group in groups:
        handles += [eng.submit(SceneRequest(len(handles) + i, scene,
                                            tenant=tenant, priority=prio,
                                            deadline_ms=dl))
                    for i, (tenant, prio, dl, scene) in enumerate(group)]
        eng.serve(max_waves=1)
    eng.serve()  # drain the backlog
    return handles


def _emit_arm(arm: str, eng, n_submitted: int):
    slo = eng.slo_stats()
    shed = ",".join(f"{k}:{v}" for k, v in
                    sorted(slo["shed_by_reason"].items())) or "none"
    emit(f"admission/{arm}_p99_ms", slo["p99_ms"] * 1e3,
         f"p50={slo['p50_ms']:.0f}ms p99={slo['p99_ms']:.0f}ms "
         f"goodput={slo['goodput_frac']:.2f} "
         f"({slo['n_completed']}/{n_submitted} done, shed {shed}) "
         f"compilations={eng.n_compilations}")
    return slo


def run(quick: bool = False):
    # base_rate is chosen to overload one-wave-per-tick service: backlog
    # builds through the diurnal peak, which is exactly where admission
    # (cheap small-bucket waves + deadline shedding) has something to win
    n_ticks, base_rate = (10, 4.0) if quick else (24, 5.0)
    batch = 2
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP,
                     n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    family = engine.SignatureFamily((SMALL_CAP, CAP))
    policy = AdmissionPolicy(max_queue=None, shed_expired=True,
                             tenant_weights={"paid": 3.0, "free": 1.0})

    def fifo_engine():
        # pre-redesign baseline: every scene padded to one max-capacity
        # signature, FIFO admission, no SLO awareness
        return SceneEngine(cfg, params, batch=batch, sync=True)

    def bucketed_engine():
        return SceneEngine(cfg, params, batch=batch, sync=True,
                           family=family, policy=policy)

    # warm both arms' jit signatures on throwaway waves, then calibrate
    # deadlines off a measured warm full-capacity wave (fresh scenes, so
    # plan build is included) — SLOs track the host instead of hardcoding
    # milliseconds
    warm = fifo_engine()
    warm.submit([SceneRequest(i, _scene_with(9000 + i, 500))
                 for i in range(batch)])
    warm.serve()
    warm.submit([SceneRequest(batch + i, _scene_with(9500 + i, 500))
                 for i in range(batch)])
    warm.serve()
    st = warm.scheduler.stats[-1]
    wave_ms = st.plan_ms + st.inflight_ms
    warm.close()
    wb = bucketed_engine()
    wb.submit([SceneRequest(i, _scene_with(9000 + i, s))
               for i, s in enumerate((150, 150, 500, 500))])
    wb.serve()
    wb.close()
    deadlines = {"paid": 5.0 * wave_ms, "free": 12.0 * wave_ms}
    emit("admission/calibration", wave_ms * 1e3,
         f"warm full-capacity wave {wave_ms:.0f}ms; deadlines "
         f"paid={deadlines['paid']:.0f}ms free={deadlines['free']:.0f}ms")

    rng = np.random.default_rng(7)
    groups = _traffic(rng, n_ticks, base_rate, deadlines)
    n_submitted = sum(len(g) for g in groups)
    n_small = sum(1 for g in groups for r in g
                  if int(np.asarray(r[3].mask).sum()) <= SMALL_CAP)
    emit("admission/traffic", 0.0,
         f"{n_submitted} requests over {n_ticks} ticks "
         f"({n_small} small, {n_submitted - n_small} large; diurnal 1-3x)")

    fifo = fifo_engine()
    _drive(fifo, groups)
    slo_f = _emit_arm("fifo", fifo, n_submitted)
    fifo.close()

    buck = bucketed_engine()
    handles = _drive(buck, groups)
    slo_b = _emit_arm("bucketed", buck, n_submitted)
    # every submitted request is accounted for: completed or surfaced shed
    assert all(h.done() for h in handles)
    assert slo_b["n_completed"] + slo_b["n_shed"] == n_submitted
    assert buck.n_compilations <= family.n_buckets
    buck.close()

    p99_speedup = slo_f["p99_ms"] / max(slo_b["p99_ms"], 1e-9)
    emit("admission/bucketed_vs_fifo", 0.0,
         f"p99 {slo_f['p99_ms']:.0f}ms -> {slo_b['p99_ms']:.0f}ms "
         f"({p99_speedup:.2f}x) goodput {slo_f['goodput_frac']:.2f} -> "
         f"{slo_b['goodput_frac']:.2f} "
         f"goodput_rps {slo_f['goodput_rps']:.1f} -> "
         f"{slo_b['goodput_rps']:.1f}")


def main(argv=None) -> None:
    standalone_bench_main(run, "bench_admission",
                          "short ramp / fewer ticks (the CI smoke job)",
                          description=__doc__, argv=argv)


if __name__ == "__main__":
    main()
