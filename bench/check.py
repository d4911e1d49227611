"""Whether the timed path served the right logits.

Once the window has closed, a sample of the requests it finished (drawn
from the seed, with the largest room or each stream's last frame in it) is
run through the plain reference (the architecture's ``logits``,
``bench/plug.py``: float32 at ``highest`` precision) from the same inputs
and weights, and the served logits of every active voxel are compared with
it. The number compared, ``gap``, is the widest |served - reference| over
the sample, as a share of the reference's largest |logit| in that request.

The configuration file's ``check.limits`` holds the limit; PERF.md gives
the readings it was set from: the program's over a dozen seeds and more,
and the control's (``CONTROL``: the reference with every matmul operand
rounded to float8 e4m3, the step below the bfloat16 that the configuration
states). ``BF16``, the reference with bfloat16 operands and bfloat16
activations, is read beside it: it shows what the check cannot tell from
float32, and why the configuration states bfloat16.
"""
from __future__ import annotations

import numpy as np

CONTROL = {"operand_dtype": "float8_e4m3fn"}
BF16 = {"operand_dtype": "bfloat16", "store_dtype": "bfloat16"}


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest |got - want| over the reference's largest |value|; inf where
    the served logits are not finite or the shapes differ."""
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def sample(traffic, win):
    """(record, active coords, active feats, active-row mask) of each
    sampled request."""
    recs = {r.ordinal: r for r in win.records}
    for o in traffic.check_ordinals(win):
        coords, feats, mask = traffic.scene_of(recs[o])
        yield recs[o], coords[mask], feats[mask], mask


def check(traffic, win, w, cfg, model) -> dict:
    """The verdict on one run: each compared number beside its limit;
    ``model`` is the architecture's module."""
    limit = cfg["check"]["limits"]["gap"]
    worst, n = 0.0, 0
    for rec, coords, feats, mask in sample(traffic, win):
        n += 1
        if rec.logits is None:
            worst = float("inf")
            continue
        want = model.logits(w, coords, feats, cfg)
        worst = max(worst, gap(np.asarray(rec.logits)[mask], want))
    numbers = [{"name": "gap", "value": worst, "limit": limit,
                "ok": worst <= limit}]
    return {"correct": n > 0 and worst <= limit, "numbers": numbers,
            "n_checked": n}
