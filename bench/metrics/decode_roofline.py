"""Kernels: the decode calls' least possible time on this chip (the larger
of required operations over peak and required bytes over bandwidth, from
``counts.decode_call``) as a share of their device time in the slice."""


def read(run):
    lane = (run.slice or {}).get("lanes", {}).get("decode")
    if not lane or lane["device_s"] <= 0:
        return None
    return 100.0 * lane["work"].seconds(run.peak) / lane["device_s"]
