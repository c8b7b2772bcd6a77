"""Adapter bank: admissions whose adapter was resident over all
admissions with an adapter, in the window and its drain, in percent."""


def read(w):
    if w.kind != "serve":
        return None
    c = w.counters
    seen = c["bank_hits"] + c["bank_misses"]
    if seen <= 0:
        return None
    return 100.0 * c["bank_hits"] / seen
