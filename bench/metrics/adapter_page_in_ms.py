"""Adapter bank: the mean time of one page-in (the tenant's factors
written into the bank's stacks on the chip, and waited for), in ms, over
the window and its drain. Reads the bank's ``page_in_ms_sum`` and
``page_in_count`` from the run's counters; None where no adapter paged
in, or the run carries no such counters."""


def read(w):
    if w.kind != "serve":
        return None
    n = w.counters.get("page_in_count")
    if not n:
        return None
    return w.counters["page_in_ms_sum"] / n
