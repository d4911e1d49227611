"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is 0.0 for
analytical/model benchmarks; see each module's docstring for the mapping to
the paper's tables and what is measured vs modeled).

``--quick`` runs the subset CI uses as a non-blocking smoke (fast modules
only) so perf scripts cannot silently rot; ``--only`` picks modules by name;
``--json PATH`` additionally writes the rows as a JSON artifact (the CI
smoke job uploads it so the perf trajectory accumulates across commits).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

# modules cheap enough for the CI smoke job (reduced configs, small scenes).
# bench_serving, bench_admission, bench_sspnna, bench_sharded_scene,
# bench_streaming, bench_dispatch and bench_faults are smoked separately
# (their own --quick CLIs write BENCH_serving.json / BENCH_admission.json /
# BENCH_sspnna.json / BENCH_sharded_scene.json / BENCH_streaming.json /
# BENCH_dispatch.json / BENCH_faults.json — the last in the chaos job) so
# they aren't duplicated here.
QUICK = ("bench_soar", "bench_spade_attrs", "bench_moe", "bench_dataflow")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="fast subset (the CI smoke job)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names, e.g. bench_coir")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact (CI perf log)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (
        bench_admission,
        bench_coir,
        bench_dataflow,
        bench_dispatch,
        bench_faults,
        bench_lm,
        bench_moe,
        bench_scn,
        bench_serving,
        bench_sharded_scene,
        bench_soar,
        bench_spade_attrs,
        bench_sspnna,
        bench_streaming,
    )

    modules = [bench_dispatch, bench_coir, bench_soar, bench_spade_attrs,
               bench_dataflow, bench_sspnna, bench_scn, bench_serving,
               bench_admission, bench_sharded_scene, bench_streaming,
               bench_faults, bench_moe, bench_lm]
    if args.only:
        wanted = {m.strip() for m in args.only.split(",")}
        known = {m.__name__.split(".")[-1] for m in modules}
        unknown = wanted - known
        if unknown:
            ap.error(f"unknown modules {sorted(unknown)}; "
                     f"known: {sorted(known)}")
        modules = [m for m in modules if m.__name__.split(".")[-1] in wanted]
    elif args.quick:
        modules = [m for m in modules if m.__name__.split(".")[-1] in QUICK]

    print("name,us_per_call,derived")
    t0 = time.time()
    for mod in modules:
        mt = time.time()
        mod.run()
        print(f"# {mod.__name__} done in {time.time() - mt:.1f}s",
              file=sys.stderr)
    total_s = time.time() - t0
    print(f"# total {total_s:.1f}s", file=sys.stderr)

    if args.json:
        from benchmarks.common import ROWS
        payload = {
            "schema": "bench-rows/v1",
            "unix_time": int(t0),
            "total_seconds": round(total_s, 2),
            "modules": [m.__name__.split(".")[-1] for m in modules],
            "rows": [{"name": n, "us_per_call": u, "derived": d}
                     for n, u, d in ROWS],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload['rows'])} rows to {args.json}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
