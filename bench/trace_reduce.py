"""Reduce a profiler trace (``.xplane.pb``) of a benchmark window to numbers.

Which planes and lines hold what (read by hand from a TPU v5e trace):

* each chip is a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds one
  event per operation the chip ran, named by the whole HLO instruction
  text (``%while.182 = (...) while(...)``). Events nest: a ``while`` loop's
  event holds the events of the ops in its body;
* the fused SSpNNA kernel is a Mosaic custom call,
  ``%closed_call.N = ... custom-call(...), custom_call_target=
  "tpu_custom_call"``: its name is not in the trace, so ``KERNELS`` matches
  the call target (it is the only Mosaic kernel on the served path);
* the ``Async XLA Ops`` line holds copies in flight, which overlap compute
  and are not counted as busy;
* the host plane ``/host:CPU`` holds the benchmark's own spans
  (``bench.submit``, ``bench.wait``), which bound the measured window.

``reduce_file`` gives the device busy time (the union of op intervals, inside
the window, averaged over the chips), the window's length, summed device
time per op name and per kernel, collective time, and the ``breakdown``:
the ten ops that took most time and the ten longest idle gaps of chip 0,
each labelled by the benchmark span the host was in (``bench/spans.py``'s
``reduce_window`` labels them by the program's spans).
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

#: kernel -> substrings of the device op names that are its calls
KERNELS = {"sspnna": ('custom_call_target="tpu_custom_call"',)}
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")
OP_LINES = ("XLA Ops",)
SPANS = ("bench.submit", "bench.wait")
_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?: =|$)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def load(path: str):
    """The trace of an ``.xplane.pb`` file, read once."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(pd):
    """-> (device ops {chip: [(name, start, end)]}, host spans
    [(name, start, end)]) of a trace, times in ns."""
    ops, spans = defaultdict(list), []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name in OP_LINES:
                chip = int(m.group(1))
                for e in line.events:
                    ops[chip].append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
            elif not m:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return ops, spans


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def base_name(name: str) -> str:
    """A short op name: the HLO instruction's name without its instance
    number, and a custom call's target: ``%fusion.12 = ...`` -> ``fusion``,
    ``%closed_call.3 = ... custom_call_target="tpu_custom_call"`` ->
    ``closed_call[tpu_custom_call]``."""
    m = _NAME.match(name)
    short = m.group(1) if m else name[:40]
    t = _TARGET.search(name)
    return f"{short}[{t.group(1)}]" if t else short


def self_times(events):
    """(name, self seconds) of nested events: a parent's time less the time
    of the events inside it."""
    out, stack = [], []  # stack of [end, index]
    for i, (name, s, e) in enumerate(sorted(events, key=lambda x: (x[1],
                                                                    -x[2]))):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out.append([name, (e - s)])
        if stack:
            out[stack[-1][1]][1] -= (e - s)
        stack.append([e, i])
    return [(n, d * 1e-9) for n, d in out]


def reduce_events(ops: dict, spans: list, n_chips: int) -> dict:
    chips = sorted(ops)[:n_chips]
    if spans:
        lo = min(s for _, s, _ in spans)
        hi = max(e for _, _, e in spans)
    else:
        every = [x for c in chips for x in ops[c]]
        lo = min(s for _, s, _ in every)
        hi = max(e for _, _, e in every)
    window = (hi - lo) * 1e-9
    busy, per_op, kernel, coll = [], defaultdict(float), defaultdict(float), 0.0
    for c in chips:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops[c]
                  if e > lo and s < hi]
        iv = union([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in iv) * 1e-9)
        for name, d in self_times(inside):
            per_op[base_name(name)] += d / len(chips)
        for name, s, e in inside:
            d = (e - s) * 1e-9 / len(chips)
            for k, pats in KERNELS.items():
                if any(p in name for p in pats):
                    kernel[k] += d
            if any(p in name for p in COLLECTIVES):
                coll += d
    busy_s = sum(busy) / max(len(busy), 1)
    gaps = []
    if chips:
        iv = union(_clip([(s, e) for _, s, e in ops[chips[0]]], lo, hi))
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)

    def label(s, e):
        best, name = 0, "no bench span"
        for n, a, b in spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, n
        return name

    return {
        "busy_s": busy_s,
        "window_s": window,
        "op_s": dict(per_op),
        "kernel_s": {k: kernel.get(k, 0.0) for k in KERNELS},
        "collective_s": coll,
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                per_op.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [[label(s, e), d * 1e-9]
                          for d, s, e in gaps[:10]],
        },
    }


def find_trace(tdir: str) -> str:
    paths = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return sorted(paths)[-1]


def reduce_file(path: str, n_chips: int) -> dict:
    ops, spans = _events(load(path))
    if not ops:
        raise ValueError(f"{path}: no device ops on a /device:TPU plane")
    return reduce_events(ops, spans, n_chips)
