"""Scheduler: tokens the decode steps produced over the rows they ran
(decode steps x max_batch), over the window and its drain, in percent."""


def read(w):
    if w.kind != "serve":
        return None
    c = w.counters
    steps = c["decode_steps"]
    if steps <= 0:
        return None
    decoded = c["tokens_generated"] - c["prefills"]   # first tokens come
    return 100.0 * decoded / (steps * w.max_batch)    # from the prefill
