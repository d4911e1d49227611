"""Planner: wall time of the program's ``plan.order`` spans (the SOAR row
ordering of each kernel level, ``WaveStats.plan_phase_ms``) per finished
request. A program without the phase counter reads nothing."""


def read(ctx):
    phases = [getattr(w, "plan_phase_ms", None) for w in ctx["window"].waves]
    ms = sum(p.get("plan.order", 0.0) for p in phases if p)
    if not ctx["done"] or ms <= 0:
        return None
    return ms / len(ctx["done"])
