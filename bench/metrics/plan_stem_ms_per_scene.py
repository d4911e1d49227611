"""Planner: wall time of the program's ``plan.stem`` spans (the table of a
stem with a kernel of its own, MinkUNet's 5^3, and its tiles where the
kernel runs it; ``WaveStats.plan_phase_ms``) per finished request. A
program or a config without a stem site reads nothing."""


def read(ctx):
    phases = [getattr(w, "plan_phase_ms", None) for w in ctx["window"].waves]
    ms = sum(p.get("plan.stem", 0.0) for p in phases if p)
    if not ctx["done"] or ms <= 0:
        return None
    return ms / len(ctx["done"])
