"""Dispatch: host milliseconds to issue one lane call (the program's
``lane_step_ms`` histogram, taken before the call's result is pulled, so
an enqueue time), over the decode and prefill calls of the window."""


def read(run):
    total = calls = 0.0
    for lane, (s1, n1) in run.c1.lane_ms.items():
        s0, n0 = run.c0.lane_ms[lane]
        total += s1 - s0
        calls += n1 - n0
    return total / calls if calls > 0 else None
