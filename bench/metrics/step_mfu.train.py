"""Model step: the training work of the window's steps (``flops.
train_step_flops``: 4 N per token over the frozen base, the LM head, the
rotations forward and backward, causal attention; recomputation not
counted) as a share of the chip's bf16 peak over the window, in
percent."""
from harness import flops


def read(w):
    if w.kind != "train" or w.steps <= 0:
        return None
    f = w.steps * flops.train_step_flops(flops.widths(w.config), w.rows,
                                         w.seq)
    return 100.0 * f / (w.seconds_measured * w.peaks["bf16_flops"] * w.chips)
