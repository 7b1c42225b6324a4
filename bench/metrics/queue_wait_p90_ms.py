"""Batcher: 90th percentile of admission minus due time, in milliseconds,
over the requests admitted in the window (the harness's own stamps)."""

import numpy as np


def read(run):
    w0, w1 = run.window
    waits = [
        s.admitted - s.due for s in run.served
        if s.admitted is not None and w0 <= s.admitted < w1
    ]
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3
