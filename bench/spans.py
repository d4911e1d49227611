#!/usr/bin/env python3
"""The program's spans in a profiler trace, and the chip's idle gaps named
by them.

    python bench/spans.py path/to/x.xplane.pb

A program that records spans (``repro.analysis.spans``) leaves one event on
the host plane for each serving stage and plan phase, named ``serve.*`` or
``plan.*``, with ``rid``, ``wave`` and ``cpu_ms`` among its stats (a
profiler that folds metadata into the name writes ``name#k=v,...#``; the
name is read up to the ``#``). ``reduce_file`` reads them beside the device
ops and the benchmark's spans that ``trace_reduce`` reads, in the same
window (the first benchmark span to the end of the last), and gives:

* ``idle_gaps``: the ten longest idle gaps of chip 0, each labelled by
  ``label``: the ``serve.*`` span of the serving thread that overlaps the
  gap most; when that is ``serve.plan_wait``, followed by ``>`` and the leaf
  ``plan.*`` phase the planner threads spent most of the gap in
  (``serve.plan_wait>plan.order``); with no program span over the gap, the
  benchmark's span, as ``trace_reduce`` labels it;
* ``idle_plan_wait_pct``: the chip's idle time under ``serve.plan_wait``
  over all its idle time in the window (``None`` in a trace without the
  program's spans);
* ``plan_ms``: plan time per request by phase and level (``-`` for phases
  outside a level), and ``plan_cpu_pct``, the ``plan.request`` spans' CPU
  time over their wall time.

``reduce_window``, which ``run.py`` calls on a traced run, reads the
trace once and gives ``trace_reduce``'s numbers with these beside them, the
breakdown's idle gaps labelled by this rule.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

import trace_reduce as tr

PREFIXES = ("serve.", "plan.")
PLAN_WAIT = "serve.plan_wait"
_META = re.compile(r"([^#]*)#(.*)#$")


def split_name(raw: str) -> tuple[str, dict]:
    """``name#k=v,k2=v2#`` -> (``name``, {k: v, ...}); a plain name has no
    metadata. Values stay strings."""
    m = _META.match(raw)
    if not m:
        return raw, {}
    meta = dict(kv.split("=", 1) for kv in m.group(2).split(",") if "=" in kv)
    return m.group(1), meta


def program_spans(pd) -> list[tuple]:
    """-> [(name, line, start_ns, end_ns, meta)] of the host planes'
    ``serve.*`` and ``plan.*`` events of a trace (``trace_reduce.load``);
    ``line`` (plane, index) tells the threads apart."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                name, meta = split_name(e.name)
                if name.startswith(PREFIXES):
                    meta.update(dict(e.stats))
                    out.append((name, (plane.name, li), e.start_ns,
                                e.start_ns + e.duration_ns, meta))
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0, min(a1, b1) - max(a0, b0))


def leaves(spans: list[tuple]) -> list[tuple]:
    """The spans with no other span of their thread inside them (spans of
    one thread nest, so the next one to start lies inside or after)."""
    by_line = defaultdict(list)
    for sp in spans:
        by_line[sp[1]].append(sp)
    out = []
    for line in by_line.values():
        line.sort(key=lambda sp: (sp[2], -sp[3]))
        for sp, nxt in zip(line, line[1:] + [None]):
            if nxt is None or nxt[2] >= sp[3]:
                out.append(sp)
    return out


def label(gap: tuple, spans: list[tuple], bench_spans: list[tuple]) -> str:
    """The label of idle gap ``(start, end)`` (the rule is the module
    docstring's)."""
    s, e = gap
    best, name = 0, None
    for sp in spans:
        ov = _overlap(sp[2], sp[3], s, e)
        if sp[0].startswith("serve.") and ov > best:
            best, name = ov, sp[0]
    if name is None:
        best, name = 0, "no bench span"
        for n, a, b in bench_spans:
            ov = _overlap(a, b, s, e)
            if ov > best:
                best, name = ov, n
        return name
    if name == PLAN_WAIT:
        phase = defaultdict(float)
        for sp in leaves([sp for sp in spans if sp[0].startswith("plan.")]):
            phase[sp[0]] += _overlap(sp[2], sp[3], s, e)
        top = max(phase.items(), key=lambda kv: kv[1], default=(None, 0))
        if top[1] > 0:
            name = f"{name}>{top[0]}"
    return name


def idle_gaps(ops: dict, lo: float, hi: float) -> list[tuple]:
    """[(start, end)] of chip 0's idle time in [lo, hi]."""
    chip = sorted(ops)[0]
    iv = tr.union(tr._clip([(s, e) for _, s, e in ops[chip]], lo, hi))
    edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def plan_table(spans: list[tuple], lo: float, hi: float) -> dict:
    """Plan ms per request of the window by phase and level, and the
    planner's CPU share."""
    reqs = [sp for sp in spans if sp[0] == "plan.request"
            and lo <= sp[2] < hi]
    if not reqs:
        return {}
    levels = [sp for sp in spans if sp[0] == "plan.level"]
    table: dict = defaultdict(lambda: defaultdict(float))
    for sp in spans:
        if not sp[0].startswith("plan.") or sp[0] == "plan.request":
            continue
        if not any(r[1] == sp[1] and r[2] <= sp[2] and sp[3] <= r[3]
                   for r in reqs):
            continue
        lv = next((str(v[4].get("level")) for v in levels
                   if v[1] == sp[1] and v[2] <= sp[2] and sp[3] <= v[3]),
                  "-")
        table[sp[0]][lv] += (sp[3] - sp[2]) * 1e-6 / len(reqs)
    wall = sum(r[3] - r[2] for r in reqs) * 1e-6
    cpu = sum(float(r[4].get("cpu_ms", 0.0)) for r in reqs)
    return {"requests": len(reqs), "plan_request_ms": wall / len(reqs),
            "plan_cpu_pct": 100.0 * cpu / wall if wall > 0 else None,
            "plan_ms": {k: dict(v) for k, v in sorted(table.items())}}


def reduce_events(ops: dict, bench_spans: list, spans: list) -> dict:
    """``ops`` and ``bench_spans`` as ``trace_reduce._events`` gives them,
    ``spans`` as ``program_spans`` does."""
    lo = min(s for _, s, _ in bench_spans)
    hi = max(e for _, _, e in bench_spans)
    gaps = idle_gaps(ops, lo, hi)
    idle = sum(e - s for s, e in gaps)
    waits = tr.union([(sp[2], sp[3]) for sp in spans if sp[0] == PLAN_WAIT])
    under = sum(_overlap(a, b, s, e) for s, e in gaps for a, b in waits)
    top = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:10]
    return {
        "idle_gaps": [[label(g, spans, bench_spans), (g[1] - g[0]) * 1e-9]
                      for g in top],
        "idle_plan_wait_pct": (100.0 * under / idle if idle > 0 and spans
                               else None),
        **plan_table(spans, lo, hi),
    }


def reduce_file(path: str) -> dict:
    pd = tr.load(path)
    return reduce_events(*tr._events(pd), program_spans(pd))


def reduce_window(path: str, n_chips: int) -> dict:
    """``trace_reduce.reduce_file``'s numbers, with the breakdown's idle
    gaps labelled by the program's spans and ``idle_plan_wait_pct`` and the
    plan table beside them, from one read of the trace."""
    pd = tr.load(path)
    ops, bench_spans = tr._events(pd)
    if not ops:
        raise ValueError(f"{path}: no device ops on a /device:TPU plane")
    red = tr.reduce_events(ops, bench_spans, n_chips)
    named = reduce_events(ops, bench_spans, program_spans(pd))
    red["breakdown"]["idle_gaps"] = named.pop("idle_gaps")
    red.update(named)
    return red


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    print(json.dumps(reduce_file(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
