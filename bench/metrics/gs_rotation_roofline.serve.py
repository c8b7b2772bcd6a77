"""Kernels: the GSOFT rotation's block-diagonal stages (``bdmm`` through
``gs_fused``/``bdmm_banked``) in serving, least time from each call's
shapes over their device time in the trace, in percent."""
from harness.trace import roofline_share


def read(w):
    return roofline_share(w.trace, "gs_rotation") if w.kind == "serve" \
        else None
