"""Kernels: ``paged_flash_decode``. Its least bytes depend on the lengths
at run time: each call (one per layer per decode step) reads every key
and value its rows attend to once and the queries, and writes the
outputs. The benchmark records the rows and keys at each decode dispatch
in the traced window; their mean gives the least time per call, times
the calls the trace holds, over their device time, in percent."""


def read(w):
    if w.kind != "serve" or w.trace is None or not w.trace_window:
        return None
    t = w.trace.kernels.get("paged_attn")
    if t is None or t.seconds <= 0:
        return None
    lo, hi = w.trace_window
    log = [(n, k) for (ts, n, k) in w.probe.decode_log if lo <= ts <= hi]
    if not log:
        return None
    c = w.config
    hd = c["hidden_size"] // c["num_attention_heads"]
    kv_row = c["num_key_value_heads"] * hd * 2 * 2        # K and V, bf16
    q_row = c["num_attention_heads"] * hd * 2 * 2          # q in, out
    per_call = sum(k * kv_row + n * q_row for n, k in log) / len(log)
    least = t.calls * per_call / w.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t.seconds
