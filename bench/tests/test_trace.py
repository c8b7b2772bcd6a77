"""The trace reduction, on a recorded trace of the program at qwen2-72b
widths on one v5e: 8 chunk-prefill steps (256 tokens) and 8 decode steps
(32 rows) of the bf16 GSOFT-banked paged engine, with ``prefill_chunk``
and ``decode`` host spans."""
import gzip
import json
import pathlib
import shutil

import pytest

from harness import cells, flops, trace

DATA = pathlib.Path(__file__).parent / "data" / "serve_step.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(cells.BENCH / "peaks.json") as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    return trace.summarize_file(str(path), peaks)


def test_window_and_busy(summary):
    assert 0.5 < summary.window_s < 1.2
    assert 0 < summary.busy_s <= summary.window_s
    assert summary.modules["jit_serve_step"] == 8
    assert summary.modules["jit_chunk_step"] == 8


def test_kernel_classes_and_counts(summary):
    k = summary.kernels
    # 7 adapted projections x 2 layers x 2 block stages, per step
    assert k["gs_rotation"].calls == 2 * 14 * 16
    assert k["paged_attn"].calls == 2 * 8                  # 2 layers
    assert k["paged_attn"].least_seconds == 0.0           # lengths unknown
    share = trace.roofline_share(summary, "gs_rotation")
    assert 0 < share <= 100


def test_breakdown_shape(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    labels = [name for name, _ in b["device_ops"]]
    assert "gs_rotation" in labels and "while" not in labels
    idle = sum(s for _, s in b["idle_gaps"])
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert {"prefill_chunk", "decode"} & {n for n, _ in b["idle_gaps"]}


def test_union_and_blame_by_hand():
    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]


def test_classify_by_hand():
    x = ("bf16", (32, 1, 8192))
    blk = ("bf16", (32, 256, 32, 32))
    assert flops.classify([x], [x, blk]) == "gs_rotation"
    g = ("f32", (256, 32, 32))
    t = ("bf16", (2048, 8192))
    assert flops.classify([g], [t, t]) == "gs_rotation_grad"
    q = [("bf16", (32, 1, 8192)), ("s8", (8192, 29568)), ("f32", (1, 29568))]
    assert flops.classify([("bf16", (32, 1, 29568))], q) == "q_matmul"
    pa = [("s32", (32, 288)), ("s32", (32,)), ("bf16", (32, 64, 128)),
          ("bf16", (1281, 16, 8, 128)), ("bf16", (1281, 16, 8, 128))]
    assert flops.classify([("bf16", (32, 64, 128))], pa) == "paged_attn"
