"""Serving: share of the chip's idle time in the traced window that lies
under the program's ``serve.plan_wait`` span, the dispatch waiting on the
host planner (``bench/spans.py``). A trace without the program's spans
reads nothing."""


def read(ctx):
    return ctx["trace"].get("idle_plan_wait_pct")
