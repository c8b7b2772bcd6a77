"""Model step: the serving work the window's requests needed, as a share
of the chip's bf16 peak over the seconds from the window's opening to
the last of them finishing. Counted: every prompt token that was fed
(prompt tokens less the pool's prefix hits), every decoded token with
attention over its keys, the LM head for every output token, and the
GSOFT rotations. Left out, so the share is a lower bound: attention of
the prompt chunks, whose cached offsets the counters do not give."""
from harness import flops


def read(w):
    if w.kind != "serve":
        return None
    wd = flops.widths(w.config)
    c = w.counters
    p = w.probe
    fed = (sum(len(r.prompt) for r in w.requests)
           - c["prefix_hit_pages"] * c["page_size"])
    total = fed * flops.serve_token_flops(wd, 0, head=False)
    for r in w.requests:
        times = p.token_times.get(r.idx, ())
        if times:
            total += 2.0 * wd["d"] * wd["V"]           # first token's head
        for k in range(1, len(times)):                 # decoded tokens
            total += flops.serve_token_flops(wd, len(r.prompt) + k,
                                             head=True)
    span = w.t_drained - w.t0
    if span <= 0 or total <= 0:
        return None
    return 100.0 * total / (span * w.peaks["bf16_flops"] * w.chips)
