"""Batcher: milliseconds a step of idle chip while the batcher's own host
work ran (the innermost program span open a ``serve.batcher.*`` one:
planning, packing, committing, emitting, admitting), over the traced
slice's steps (``phases.program_idle``)."""


def read(run):
    idle = (run.slice or {}).get("program_idle")
    if not idle or not idle["steps"]:
        return None
    return 1e3 * idle["batcher"] / idle["steps"]
