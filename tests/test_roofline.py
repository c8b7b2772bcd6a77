"""Roofline machinery: trip-count-aware HLO walker + term math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_cost import module_cost
from repro.analysis.roofline import (HBM_BW, ICI_BW, PEAK_FLOPS, Roofline,
                                     advice, model_flops)


def test_walker_multiplies_scan_trip_counts():
    """XLA cost_analysis counts while bodies once; the walker must not."""
    M, TRIPS = 128, 7

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=TRIPS)
        return y

    comp = jax.jit(f).lower(jnp.ones((M, M)), jnp.ones((M, M))).compile()
    xla_flops = comp.cost_analysis().get("flops", 0)
    walk = module_cost(comp.as_text())
    expect = 2 * M ** 3 * TRIPS
    assert abs(walk.flops - expect) / expect < 0.05
    assert xla_flops < walk.flops / 2      # documents the XLA undercount


def test_walker_counts_dot_contraction():
    comp = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((64, 32)), jnp.ones((32, 16))).compile()
    walk = module_cost(comp.as_text())
    expect = 2 * 64 * 32 * 16
    assert abs(walk.flops - expect) / expect < 0.2


def test_walker_bytes_reasonable():
    comp = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((256, 256)), jnp.ones((256, 256))).compile()
    walk = module_cost(comp.as_text())
    io = 3 * 256 * 256 * 4
    assert io * 0.5 <= walk.bytes <= io * 4


def test_roofline_terms_and_dominant():
    r = Roofline(arch="a", shape="s", mesh="single", chips=256,
                 flops_per_device=197e12,          # exactly 1 s of compute
                 bytes_per_device=819e9 * 2,       # 2 s of HBM
                 coll_bytes_per_device=50e9 * 0.5, # 0.5 s of ICI
                 model_flops=197e12 * 256)
    assert np.isclose(r.compute_s, 1.0)
    assert np.isclose(r.memory_s, 2.0)
    assert np.isclose(r.collective_s, 0.5)
    assert r.dominant == "memory"
    assert np.isclose(r.step_time_s, 2.0)
    assert np.isclose(r.roofline_fraction, 0.5)
    assert "HBM" in advice(r)


def test_model_flops():
    assert model_flops(int(1e9), 1000, "train") == 6e12
    assert model_flops(int(1e9), 1000, "serve") == 2e12


@pytest.mark.parametrize("dom,frag", [
    ("compute", "compute-bound"),
    ("collective", "collective-bound"),
])
def test_advice_strings(dom, frag):
    kw = dict(arch="a", shape="s", mesh="m", chips=1, model_flops=1e12,
              flops_per_device=1.0, bytes_per_device=1.0,
              coll_bytes_per_device=1.0)
    if dom == "compute":
        kw["flops_per_device"] = 1e20
    else:
        kw["coll_bytes_per_device"] = 1e20
    assert frag in advice(Roofline(**kw))
