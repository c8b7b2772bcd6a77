"""Kernels: the int8 weight matmuls (``q_matmul``, ``gs_q_matmul``), least
time from each call's shapes over their device time in the trace, in
percent."""
from harness.trace import roofline_share


def read(w):
    return roofline_share(w.trace, "q_matmul", "gs_q_matmul")
