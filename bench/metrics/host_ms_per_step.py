"""Batcher: host time per step (``BatcherStats.host_plan_ms``, the step's
wall time less its blocking device pulls) over the window's steps."""


def read(run):
    steps = run.c1.steps - run.c0.steps
    if steps <= 0:
        return None
    return (run.c1.host_plan_ms - run.c0.host_plan_ms) / steps
