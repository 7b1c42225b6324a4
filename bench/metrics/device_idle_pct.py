"""Device: the share of the traced slice in which no operation ran, averaged
over the chips the cell's plan uses."""


def read(run):
    sl = run.slice
    if not sl or sl["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - sl["busy_s"] / sl["window_s"])
