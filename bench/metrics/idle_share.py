"""Device layer: the share of the traced window in which no operation
ran on the chip, in percent (1 - busy union / window)."""


def read(run):
    red = run.red
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
