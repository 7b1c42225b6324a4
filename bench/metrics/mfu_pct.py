"""Whole step: the model operations of every decode and prefill call in
the traced slice, over the slice's length times the chips' bf16 peak
times the chips the cell's plan uses."""


def read(run):
    sl = run.slice
    if not sl or sl["window_s"] <= 0 or not run.peak:
        return None
    flops = sum(lane["work"].flops for lane in sl["lanes"].values())
    peak = run.peak["bf16_flops_per_s"] * len(run.chips_used)
    return 100.0 * flops / (sl["window_s"] * peak)
