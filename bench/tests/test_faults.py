"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference one precision down) reads wider than the
program: small widths on the CPU, the harness's look for a chip skipped,
the rest of a run driven as on the chip. Each limit here is set from a
sound run of the same size, as the chip's limits are from the chip's."""
import time

import pytest

from harness import faults, runner
from small import DEVICE, PEAKS, model_config, small_cell


@pytest.fixture(autouse=True)
def no_peaks_table(monkeypatch):
    monkeypatch.setattr(runner.device_lib, "peaks", lambda kind: PEAKS)


def run(kind, limits, monkeypatch, fault=None, controls=(), seed=2**33):
    cell = small_cell(*{"serve": ("qwen2-72b", "chat"),
                         "train": ("qwen2-72b", "gsoft-train")}[kind])
    monkeypatch.setattr(runner, "limits", lambda _: {
        "limits": {k: {"limit": v} for k, v in limits.items()}})
    undo = faults.FAULTS[fault]() if fault else (lambda: None)
    try:
        fn = runner.run_serve if kind == "serve" else runner.run_train
        return fn(cell, seed, 2.0, False, time.perf_counter(), DEVICE,
                  cfg=model_config(cell), controls=controls)
    finally:
        undo()


@pytest.fixture(scope="module")
def mp():
    with pytest.MonkeyPatch.context() as m:
        yield m


@pytest.fixture(scope="module")
def serve_sound(mp):
    mp.setattr(runner.device_lib, "peaks", lambda kind: PEAKS)
    open_lim = {"mean_gap": 1e9, "bad_outputs": 0, "unchecked": 0}
    return run("serve", open_lim, mp, controls=("int4",))


def serve_limits(sound):
    gap = sound["checks"]["mean_gap"]["value"]
    return {"mean_gap": 10 * gap + 1e-4, "bad_outputs": 0, "unchecked": 0}


def test_serve_sound_and_control(serve_sound, monkeypatch):
    assert serve_sound["correct"]
    res = run("serve", serve_limits(serve_sound), monkeypatch,
              controls=("int4",))
    assert res["correct"]
    assert not res["control"]["int4"]["correct"], res["control"]


def test_serve_token_altered(serve_sound, monkeypatch):
    res = run("serve", serve_limits(serve_sound), monkeypatch,
              fault="token_altered")
    assert not res["correct"]
    assert res["checks"]["mean_gap"]["value"] > \
        res["checks"]["mean_gap"]["limit"]


@pytest.fixture(scope="module")
def train_sound(mp):
    mp.setattr(runner.device_lib, "peaks", lambda kind: PEAKS)
    open_lim = {"loss_gap": 1e9, "grad_norm_gap": 1e9,
                "update_norm_gap": 1e9}
    return run("train", open_lim, mp, controls=("int4",))


def train_limits(sound):
    return {k: 10 * v["value"] + 1e-6 for k, v in sound["checks"].items()}


def test_train_sound_and_control(train_sound):
    assert train_sound["correct"]
    lim = train_limits(train_sound)
    ctrl = train_sound["control"]["int4"]["checks"]
    assert any(ctrl[k]["value"] > lim[k] / 10 for k in lim)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_faults(train_sound, monkeypatch, fault):
    res = run("train", train_limits(train_sound), monkeypatch, fault=fault)
    assert not res["correct"], res["checks"]
