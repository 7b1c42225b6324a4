"""Kernels: the prefill calls' least possible time on this chip (from
``counts.prefill_call``) as a share of their device time in the slice."""


def read(run):
    lane = (run.slice or {}).get("lanes", {}).get("prefill")
    if not lane or lane["device_s"] <= 0:
        return None
    return 100.0 * lane["work"].seconds(run.peak) / lane["device_s"]
