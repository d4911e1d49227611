#!/usr/bin/env python3
"""Run one benchmark cell once.

    python bench/run.py --workload m16.rooms --seed 7 --seconds 50 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). The configuration's ``arch`` names its
architecture's two modules (``bench/archs/<arch>.py`` and
``<arch>_sut.py``, ``bench/plug.py``): its weights, reference and work
counts, and the program that serves it; the harness reads only the
configuration's neutral keys (``full_scale``, ``capacity``,
``input_features``, ``nClasses``, ``batch``, ``check.limits``). The
per-layer metrics are read by ``bench/metrics/<base>.py``, where ``<base>``
is the metric's name up to its first dot: ``idle_pct.rooms`` and a later
``idle_pct.stream`` share ``idle_pct.py``. Adding a cell, config, mix,
metric or architecture adds files and entries; no code here changes.

A run: fail unless JAX finds a TPU with the cell's chips; turn on the
persistent compile cache; make the weights and the traffic from the seed;
pin the plan spec and warm up (``setup_s`` ends here); send the traffic
(rooms: a fixed number sized from ``--seconds``; streams: for ``--seconds``)
and wait for what is in flight; read the device's peak
memory; stop the server; compare a sample of the served logits with the
plain reference (``bench/check.py``). ``--trace 1`` records the window with
the profiler and reports the per-layer metrics instead of the end-to-end
ones. The last line of stdout is the result as one JSON object; the numbers
compared, each beside its limit, are the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import plug  # noqa: E402


class BenchError(RuntimeError):
    """The run cannot produce a result."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry of BENCHMARK.json with its config, mix and metrics
    resolved from their files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(cells[name])
    here = root / "bench"
    cell["cfg"] = json.loads(
        (here / "configs" / f"{cell['config']}.json").read_text())
    cell["mix"] = json.loads(
        (here / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


def reader(metric: str, root: Path = ROOT):
    """``read`` of ``bench/metrics/<base>.py``, the metric's name up to its
    first dot (the rest names the cells' family)."""
    base = metric.split(".", 1)[0]
    return plug.load(root / "bench" / "metrics" / f"{base}.py").read


def device_check(chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {dev.platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def e2e_value(name: str, win, setup_s: float):
    """An end-to-end metric by its name: ``setup_s`` or ``<unit>s_per_s``
    (requests finished over the window)."""
    if name == "setup_s":
        return setup_s
    if name.endswith("s_per_s"):
        done = [r for r in win.records if r.status == "completed"]
        return len(done) / win.seconds
    raise BenchError(f"no rule for end-to-end metric {name!r}")


class CompileClock:
    """Counts XLA backend compiles and their seconds (jax.monitoring)."""

    def __init__(self):
        import jax

        self.events: list = []   # (function name, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((fun_name, duration))


def peak_memory(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, devices=None, on_engine=None,
             session: dict | None = None) -> dict:
    """One run of one cell -> the result object. ``devices`` skips the look
    for a chip (tests pass the CPU's); ``on_engine(engine)`` is called on
    the built engine before the warm-up (tests plant faults through it).
    ``session`` lets several runs share one process (``calibrate.py``): it
    keeps the pinned spec, which no seed changes, and receives the run's
    traffic, window, weights, config and architecture modules."""
    import jax

    import check
    import drive
    import sut
    import weights

    cell = load_cell(name, root)
    if devices is None:
        devices = device_check(cell["chips"])
    print(f"compile cache: {sut.use_compile_cache()}", file=sys.stderr)
    # every program, however fast it compiles, is found again next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = CompileClock()
    cfg, mix = cell["cfg"], cell["mix"]
    model = plug.arch(cfg, root)
    prog = sut.program(cfg, root)
    mcfg = prog.model_config(cfg)
    seed = seed % (2 ** 63)

    phases = {"start": time.perf_counter() - T_START}
    w = weights.make_weights(seed, model.weight_shapes(cfg))
    params = prog.params(w, cfg)
    phases["weights"] = time.perf_counter() - T_START
    traffic = drive.traffic(mix, seed, seconds, cfg)
    phases["traffic"] = time.perf_counter() - T_START
    specs = {} if session is None else session.setdefault("specs", {})
    key = (cell["config"], json.dumps(mix["pin"], sort_keys=True))
    if key not in specs:
        specs[key] = prog.pin_spec(mcfg, drive.pin_rooms(mix, cfg))
    spec = specs[key]
    phases["pin"] = time.perf_counter() - T_START
    eng = prog.build_engine(mcfg, params, cfg["batch"], spec)
    if on_engine is not None:
        on_engine(eng)
    eng.serve_forever()
    try:
        traffic.warm_up(eng)
        n_waves0 = len(eng.wave_stats)
        setup_s = time.perf_counter() - T_START
        phases["warm"] = setup_s
        n_compiles = len(clock.events)
        tdir = None
        if trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the spans of bench/, not the runtime's
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            win = traffic.run(eng, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        win.waves = list(eng.wave_stats[n_waves0:])
        in_window = clock.events[n_compiles:]
        memory = peak_memory(devices[:cell["chips"]])
        n_compiled = eng.n_compilations
    finally:
        eng.close()
    del eng, params

    done = [r for r in win.records if r.status == "completed"]
    failed = len(win.records) - len(done)
    modes = {}
    for r in done:
        modes[r.plan_mode] = modes.get(r.plan_mode, 0) + 1
    compile_s = sum(d for _, d in clock.events[:n_compiles])
    print(f"set-up {setup_s:.3f} s (phases ended at, s: "
          f"{ {k: round(v, 3) for k, v in phases.items()} }), of it "
          f"{compile_s:.3f} s in {n_compiles} compiles or cache loads; "
          f"window: {len(win.records)} requests, {len(done)} completed in "
          f"{win.seconds:.3f} s, compiles or cache loads inside it: "
          f"{in_window}; the wave program has {n_compiled} signature(s); "
          f"stream plans {modes}", file=sys.stderr)

    metrics = {}
    if trace:
        import spans
        import trace_reduce
        import work

        red = spans.reduce_window(trace_reduce.find_trace(tdir),
                                  n_chips=cell["chips"])
        shutil.rmtree(tdir, ignore_errors=True)
        pk = work.peaks(devices[0].device_kind)
        ctx = {"window": win, "done": done,
               "trace": red, "chips": cell["chips"], "peaks": pk,
               "work": work.window_work(traffic, done, cfg, model,
                                        prog.kernel_sites(spec), pk)}
        for m in cell["per_layer"]:
            v = reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e_value(m["name"], win, setup_s),
                                  "unit": m["unit"]}

    traffic.release()
    gc.collect()
    verdict = check.check(traffic, win, w, cfg, model)
    print(f"checked {verdict['n_checked']} requests against the reference",
          file=sys.stderr)
    dev = devices[0]
    result = {
        "correct": verdict["correct"],
        "attempted": len(win.records),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory},
    }
    if trace:
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    result["check"] = verdict["numbers"]
    if session is not None:
        session.update(traffic=traffic, window=win, weights=w, cfg=cfg,
                       model=model, program=prog)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in result["check"]:
        print(f"check {line['name']}: {line['value']!r} limit "
              f"{line['limit']!r} ({'ok' if line['ok'] else 'FAIL'})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
