"""Tensor-parallel serve meshes + the serving-side ``shard_map`` wrapper.

``serve_mesh(tp)`` is what ``launch/serve.py --tp N`` builds: a 1 x N
("data", "model") mesh. A ``ModelRuntime`` constructed with it commits
params / KV state / bank factors per ``sharding.specs`` and lets GSPMD
partition the jitted prefill/decode closures — no retracing, engines run
unchanged.

``head_shard_map`` is the explicit-collective escape hatch for kernels
whose launch geometry must see the LOCAL shard (Pallas paged attention
over the kv-head split): it maps a per-shard function over one named
axis of its array arguments. Together with ``sharding/``, this module is
the only place allowed to construct ``shard_map`` (CI grep guard) — the
point is that partitioning POLICY never leaks into kernels or engines.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import make_mesh


def serve_mesh(tp: int, dp: int = 1, devices=None) -> Mesh:
    """The serving mesh for ``--tp N``: (dp, tp) over ("data", "model").
    tp=1 still yields a real (degenerate) mesh so the placement path is
    identical whether or not the model is actually split. ``devices``
    names the chips to use (default: the first dp*tp visible ones) — a
    1x1 mesh over one device is how a replica is committed to its chip."""
    if tp < 1 or dp < 1:
        raise ValueError(f"tp={tp} and dp={dp} must be >= 1")
    n = tp * dp
    devices = list(devices or jax.devices()[:n])
    if len(devices) != n:
        visible = jax.devices()
        raise ValueError(
            f"serve mesh needs {n} devices; got {len(devices)} (the "
            f"{visible[0].platform} backend shows {len(visible)})")
    return make_mesh(dp, tp, devices=devices)


def head_shard_map(fn: Callable, mesh: Mesh,
                   head_axes: Sequence[int], *,
                   out_head_axis: int = 1,
                   axis: str = "model") -> Callable:
    """Wrap a per-shard kernel so it runs once per 'model'-axis shard of
    its head-split arguments.

    ``head_axes[i]`` names which dim of positional argument i carries
    heads (None = that argument is replicated — page tables, positions);
    the output's head dim is ``out_head_axis``. Inside the wrapper the
    kernel sees LOCAL shapes (kv_heads / tp), which is exactly what the
    tp-tagged ``kernels.dispatch`` keys resolve tunings for — the full
    array's launch geometry can be illegal for the shard.
    """
    def spec(ax):
        if ax is None:
            return P()
        s = [None] * (ax + 1)
        s[ax] = axis
        return P(*s)

    in_specs = tuple(spec(ax) for ax in head_axes)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=spec(out_head_axis), check_vma=False)
