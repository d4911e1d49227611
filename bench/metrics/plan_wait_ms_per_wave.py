"""Serving layer: mean time a wave's dispatch waited on the planner
(``WaveStats.plan_wait_ms``), over the window's waves."""


def read(ctx):
    waves = ctx["window"].waves
    if not waves:
        return None
    return sum(w.plan_wait_ms for w in waves) / len(waves)
