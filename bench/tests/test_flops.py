"""Operations and bytes against hand counts at qwen2-72b's widths."""
import json

import pytest

from harness import cells, flops


def test_gs_rotation_decode_call():
    x = ("bf16", (32, 1, 8192))
    blk = ("bf16", (32, 256, 32, 32))
    ops, by = flops.kernel_cost("gs_rotation", [x], [x, blk])
    assert ops == 2 * 32 * 8192 * 32                       # 16,777,216
    assert by == 2 * 32 * 8192 * 2 + 32 * 256 * 32 * 32 * 2  # 17,825,792


def test_q_matmul_call():
    ops_in = [("bf16", (32, 1, 8192)), ("s8", (8192, 29568)),
              ("f32", (1, 29568))]
    out = [("bf16", (32, 1, 29568))]
    ops, by = flops.kernel_cost("q_matmul", out, ops_in)
    assert ops == 2 * 32 * 8192 * 29568
    assert by == 32 * 8192 * 2 + 8192 * 29568 + 29568 * 4 + 32 * 29568 * 2


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_time((197e12, 1.0), peaks) == pytest.approx(1.0)
    assert flops.least_time((1.0, 819e9), peaks) == pytest.approx(1.0)


def test_model_counts():
    w = flops.widths(cells.load_cell("qwen2-72b.chat").config)
    assert flops.layer_matmul_params(w) == (
        8192 * 8192 + 2 * 8192 * 1024 + 8192 * 8192 + 3 * 8192 * 29568)
    assert flops.layer_matmul_params(w) == 877_658_112
    assert flops.rotation_inputs(w) == 6 * 8192 + 29568
    # one decoded token at 1000 keys, with its LM head row
    want = 2 * (2 * 877_658_112 + 4 * 32 * 78_720 + 4 * 1000 * 64 * 128) \
        + 2 * 8192 * 152064
    assert flops.serve_token_flops(w, 1000, head=True) == want


def test_train_step_count():
    with open(cells.BENCH / "configs" / "qwen2-72b.json") as f:
        w = flops.widths(json.load(f))
    per_token = (4 * 2 * 877_658_112 + 4 * 8192 * 152064
                 + 12 * 32 * 2 * 78_720)
    attn = 3 * 2 * 64 * 128 * 2048 * 2049 * 2
    assert flops.train_step_flops(w, 1, 2048) == 2048 * per_token + attn
