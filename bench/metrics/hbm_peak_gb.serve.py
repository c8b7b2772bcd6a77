"""Device: the allocator's peak bytes in use on the fullest chip over the
run, before the reference runs, in GB (1e9 bytes)."""


def read(w):
    if w.kind != "serve" or not w.memory_peak_bytes:
        return None
    return w.memory_peak_bytes / 1e9
