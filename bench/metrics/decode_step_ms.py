"""Step programs: device milliseconds per decode call (the ``cbp`` lane's
executable), summed over the traced slice's calls."""


def read(run):
    lane = (run.slice or {}).get("lanes", {}).get("decode")
    if not lane or not lane["calls"]:
        return None
    return 1e3 * lane["device_s"] / lane["calls"]
