"""Device: share of the traced window in which no operation ran on the
chip (1 - union of op intervals / window), in percent."""


def read(w):
    if w.kind != "train" or w.trace is None or w.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
