#!/usr/bin/env python3
"""Readings that the check's limits are set from, on the chip.

    python bench/calibrate.py --workload m16.rooms --seeds 1 2 3 --seconds 8

For each seed, in one process: a run of the cell with a short window (the
program's readings, as ``bench/run.py`` takes them), then two controls on
the same sampled requests, each put in the program's place: the plain
reference with every matmul operand rounded to float8 e4m3, one step below
the bfloat16 that the configuration states (``control``), and with bfloat16
operands and bfloat16 activations (``bf16``). Prints one JSON line per seed
with the readings, and writes them to
``chiprun_out/calibrate-<workload>.jsonl`` when that directory exists.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import run  # noqa: I001 - first: it starts the set-up clock
import check


def control_readings(session: dict, dtypes: dict) -> dict:
    """A control's ``gap`` on the run's sampled requests."""
    traffic, win, w, cfg = (session[k] for k in
                            ("traffic", "window", "weights", "cfg"))
    worst = 0.0
    for _, coords, feats, _ in check.sample(traffic, win):
        want = check.reference_logits(coords, feats, w, cfg)
        ctl = check.reference_logits(coords, feats, w, cfg, **dtypes)
        worst = max(worst, check.gap(ctl, want))
    return {"gap": worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    out = Path(run.ROOT) / "chiprun_out"
    session: dict = {}
    for seed in args.seeds:
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           session=session)
        row = {"workload": args.workload, "seed": seed,
               "program": {n["name"]: n["value"] for n in res["check"]},
               "control": control_readings(session, check.CONTROL),
               "bf16": control_readings(session, check.BF16),
               "attempted": res["attempted"], "failed": res["failed"],
               "metrics": res["metrics"]}
        print(json.dumps(row), flush=True)
        if out.is_dir():
            with open(out / f"calibrate-{args.workload}.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
