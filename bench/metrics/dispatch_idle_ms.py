"""Dispatch: milliseconds a step of idle chip while a ``serve.dispatch.*``
span was the innermost open (uploads, executable launches, pulls of
results), over the traced slice's steps (``phases.program_idle``)."""


def read(run):
    idle = (run.slice or {}).get("program_idle")
    if not idle or not idle["steps"]:
        return None
    return 1e3 * idle["dispatch"] / idle["steps"]
