"""Planner: the planner threads' CPU time over their wall time in the
program's ``plan.request`` spans (``WaveStats.plan_cpu_ms`` over
``plan_ms``), over the window's waves. Below 100% a plan waits while it
runs: on the GIL, a lock or the OS. A program without the counter reads
nothing."""


def read(ctx):
    waves = ctx["window"].waves
    wall = sum(w.plan_ms for w in waves)
    if wall <= 0 or not all(hasattr(w, "plan_cpu_ms") for w in waves):
        return None
    return 100.0 * sum(w.plan_cpu_ms for w in waves) / wall
