"""Cache manager: milliseconds a step of idle chip while a ``serve.cache.*``
span was the innermost open (page reservation with eviction, upkeep,
prefix lookup and publication, migration), over the traced slice's steps
(``phases.program_idle``)."""


def read(run):
    idle = (run.slice or {}).get("program_idle")
    if not idle or not idle["steps"]:
        return None
    return 1e3 * idle["cache"] / idle["steps"]
