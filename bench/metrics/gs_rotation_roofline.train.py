"""Kernels: the GSOFT rotation's stages in the train step, forward and
backward (``bdmm`` and its block-gradient kernel), least time from each
call's shapes over their device time in the trace, in percent."""
from harness.trace import roofline_share


def read(w):
    return roofline_share(w.trace, "gs_rotation", "gs_rotation_grad") \
        if w.kind == "train" else None
