"""Step programs: device milliseconds per prefill call (the ``pf`` lane's
executable), over the traced slice's calls."""


def read(run):
    lane = (run.slice or {}).get("lanes", {}).get("prefill")
    if not lane or not lane["calls"]:
        return None
    return 1e3 * lane["device_s"] / lane["calls"]
