"""Operations and bytes: of each kernel call, from the shapes its trace
event names, and of the model's useful work per token, from the
configuration's widths. Peaks come from ``peaks.json`` alone.

Kernel calls (Pallas ``tpu_custom_call`` ops), told apart by operands:

  gs_rotation       (x (..., d), blocks (..., r, b, b)) -> like x: one
                    block-diagonal stage of a GSOFT rotation (bdmm);
                    2 * tokens * d * b operations
  gs_rotation_grad  (dy (T, d), x (T, d)) -> f32 (r, b, b): the block
                    gradient of one stage; 2 * T * d * b
  q_matmul          (x (..., K), q int (K, N), scale) -> (..., N);
                    2 * M * K * N, on the bf16 units (the kernel widens
                    the codes to bf16 in VMEM)
  gs_q_matmul       (L, R, x, q, scale): rotation and int matmul fused
  paged_attn        (table, kv_len, q, k pages, v pages): its bytes depend
                    on the lengths at run time; its reader counts them

Least bytes are each operand read once and each result written once, so
a call's least time ``max(ops / peak, bytes / bandwidth)`` is never more
than it can take, and no share of it passes 100%.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

Shape = Tuple[str, Tuple[int, ...]]

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8}
INT = ("s8", "u8", "f8e4m3fn")


def nbytes(shape: Shape) -> int:
    return DTYPE_BYTES[shape[0]] * math.prod(shape[1])


def classify(res: Sequence[Shape], ops: Sequence[Shape]) -> str:
    if len(ops) == 2 and len(res) == 1:
        x, blk = ops
        bd = blk[1]
        if len(bd) >= 3 and bd[-1] == bd[-2] and res[0] == x:
            return "gs_rotation"
        rd = res[0][1]
        if (x == blk and len(x[1]) == 2 and len(rd) == 3
                and rd[-1] == rd[-2]):
            return "gs_rotation_grad"
    if len(ops) == 3 and ops[1][0] in INT:
        return "q_matmul"
    if len(ops) == 5 and ops[3][0] in INT:
        return "gs_q_matmul"
    if len(ops) == 5 and ops[0][0] == "s32" and ops[1][0] == "s32":
        return "paged_attn"
    return "kernel"


def kernel_cost(kind: str, res: Sequence[Shape], ops: Sequence[Shape]
                ) -> Optional[Tuple[float, float]]:
    """(operations, least bytes) of one call, or None where the shapes
    alone do not say (paged attention)."""
    io = float(sum(nbytes(s) for s in ops) + sum(nbytes(s) for s in res))
    if kind == "gs_rotation":
        x, blk = ops
        return 2.0 * math.prod(x[1][:-1]) * x[1][-1] * blk[1][-1], io
    if kind == "gs_rotation_grad":
        t, d = ops[1][1]
        return 2.0 * t * d * res[0][1][-1], io
    if kind == "q_matmul":
        x, q = ops[0][1], ops[1][1]
        return 2.0 * math.prod(x[:-1]) * x[-1] * q[-1], io
    if kind == "gs_q_matmul":
        L, x, q = ops[0][1], ops[2][1], ops[3][1]
        m = math.prod(x[:-1])
        return 4.0 * m * x[-1] * L[-1] + 2.0 * m * x[-1] * q[-1], io
    return None


def least_time(cost: Tuple[float, float], peaks: Dict[str, float]) -> float:
    return max(cost[0] / peaks["bf16_flops"],
               cost[1] / peaks["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# the model's useful work (a configuration file's widths)
# ---------------------------------------------------------------------------

def widths(config: Dict) -> Dict[str, int]:
    d = int(config["hidden_size"])
    H = int(config["num_attention_heads"])
    return {"d": d, "H": H, "K": int(config["num_key_value_heads"]),
            "hd": int(config.get("head_dim", d // H)),
            "f": int(config["intermediate_size"]),
            "V": int(config["vocab_size"]),
            "L": int(config["num_hidden_layers"]),
            "b": int(config["deployment"]["adapter"]["block_size"])}


def layer_matmul_params(w: Dict[str, int]) -> int:
    """Weights of one layer's projections (norms, biases, embedding out)."""
    q, kv = w["H"] * w["hd"], w["K"] * w["hd"]
    return w["d"] * q + 2 * w["d"] * kv + q * w["d"] + 3 * w["d"] * w["f"]


def rotation_inputs(w: Dict[str, int]) -> int:
    """Sum of the input widths of the seven adapted projections."""
    return 5 * w["d"] + w["H"] * w["hd"] + w["f"]


def serve_token_flops(w: Dict[str, int], kv_len: int, head: bool) -> float:
    """One token through the stack: projections, the GSOFT rotations (two
    block stages each), attention over ``kv_len`` keys, and the LM head
    where a logit row is produced."""
    per_layer = (2.0 * layer_matmul_params(w)
                 + 4.0 * w["b"] * rotation_inputs(w)
                 + 4.0 * kv_len * w["H"] * w["hd"])
    return w["L"] * per_layer + (2.0 * w["d"] * w["V"] if head else 0.0)


def train_step_flops(w: Dict[str, int], rows: int, seq: int) -> float:
    """One adapter-tuning step over a frozen base: forward and the
    backward to the activations (4 N per token; no weight gradient of
    the base), the LM head likewise, the rotations forward, backward to
    the activations and to their blocks (12 b d_in), and causal
    attention forward and backward (three times its forward). Recomputed
    work is not counted."""
    tokens = rows * seq
    per_token = (4.0 * w["L"] * layer_matmul_params(w)
                 + 4.0 * w["d"] * w["V"]
                 + 12.0 * w["b"] * w["L"] * rotation_inputs(w))
    attn_fwd = 2.0 * w["H"] * w["hd"] * seq * (seq + 1) * w["L"]
    return tokens * per_token + rows * 3.0 * attn_fwd
