"""Autotuned dispatch + custom-VJP rules for the GS kernel suite.

This module is the single place that decides *how* a GS kernel call runs:

  * ``Tuning`` — the (token_tile, group_tile) launch geometry of a call.
  * a three-level resolution order, consulted at trace time on static shapes:
        1. config overrides  (``register_tuning`` / ``install_tunings`` —
           wired from ``ModelConfig.kernel_tunings`` by train/steps.py)
        2. autotuned results (``autotune_bdmm`` / ``autotune_gs`` — a small
           cached timing search over candidate tiles, keyed per
           (shape, dtype, backend))
        3. shape heuristics  (the former ad-hoc rules from ops.py/bdmm.py)
  * ``bdmm_diff`` / ``gs_diff`` / ``gs_T_diff`` — the kernels wrapped in
    ``jax.custom_vjp`` so ``jax.grad`` through ``use_pallas=True`` runs
    Pallas in both directions instead of falling back to XLA autodiff over
    the kernel body:

        bdmm:  dx = bdmm(blocks^T, dy);  dblocks = token-contraction kernel.
        gs:    dx = Q^T dy (the transpose rotation is itself a GS transform —
               the paper's structure makes the VJP closed under the class);
               dL/dR from bdmm token-contraction kernels (fp32
               accumulation over token tiles).
        gs_T:  dx = Q dy (the forward kernel); dL/dR via the identity
               <g, Q^T x> = <x, Q g>, i.e. the same factor-gradient kernels
               with (input, cotangent) swapped.
        gs_T_rows: the row-batched banked forward; its backward is gs_T's,
               per row.
  * ``banked_gs_rows_tiles`` — which kernel a banked GS rotation takes,
    from the tokens per row.

Autotuning is *eager* (it times real kernel launches), so call it from
benchmarks / warmup code, never inside jit; lookups inside jit are pure
Python on static shapes and cost nothing at runtime.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .bdmm import bdmm_dblocks_pallas, bdmm_pallas, default_group_tile
from .gs_fused import (gs_fused_T_pallas, gs_fused_T_rows_pallas,
                       gs_fused_bwd_pallas, gs_fused_grads_pallas,
                       gs_fused_pallas)
from .q_matmul import default_n_tile, gs_q_matmul_pallas, q_matmul_pallas

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class Tuning:
    """Launch geometry for one kernel call site (hashable, jit-static)."""
    token_tile: int = 128
    group_tile: int = 0          # 0 -> per-shape heuristic (bdmm only)


Key = Tuple  # (op, *shape_sig, dtype_name, backend)

# config-provided overrides (backend/dtype wildcards) beat autotuned results,
# which beat the shape heuristic.
_OVERRIDES: Dict[Key, Tuning] = {}
_TUNED: Dict[Key, Tuning] = {}
# overrides installed from a ModelConfig — replaced wholesale on the next
# install so one config's tunings never leak into another model built in
# the same process (register_tuning entries are sticky by design)
_CONFIG_KEYS: set = set()

DEFAULT_TOKEN_TILES: Tuple[int, ...] = (64, 128, 256)


def _backend() -> str:
    return jax.default_backend()


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend
    (tests), never on the TPU. Any other backend has no path for these
    kernels, so it raises rather than silently interpreting."""
    backend = _backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                       f"on CPU; backend {backend!r} is neither")


# Serve-time tensor parallelism (ISSUE 8): under ``shard_map``/GSPMD each
# device traces the kernel on its LOCAL shard, whose shapes alias a
# different single-device problem (e.g. a tp=2 split of d_ff=256 looks like
# an unsharded d_ff=128 call). Tunings timed for one must not answer for
# the other — the launch that wins for the full array can be illegal for
# the shard — so a meshed ``ModelRuntime`` declares its TP degree here and
# every key's op name picks up an ``@tpN`` tag. tp=1 (the default, and
# every pre-existing caller) leaves op names, wildcard semantics
# (``key[:-2]``) and the persisted REPRO_TUNING_CACHE byte-identical.
_SERVE_TP: int = 1


def set_serve_tp(n: int) -> None:
    """Declare the serve-time TP degree (1 = off). Called by
    ``core.runtime.ModelRuntime`` when built with a mesh."""
    global _SERVE_TP
    _SERVE_TP = max(int(n), 1)


def serve_tp() -> int:
    return _SERVE_TP


def _op(name: str) -> str:
    return name if _SERVE_TP == 1 else f"{name}@tp{_SERVE_TP}"


def bdmm_key(r: int, bo: int, bi: int, dtype,
             backend: Optional[str] = None) -> Key:
    return (_op("bdmm"), r, bo, bi, jnp.dtype(dtype).name,
            backend or _backend())


def gs_key(r: int, b: int, dtype, backend: Optional[str] = None) -> Key:
    return (_op("gs"), r, b, jnp.dtype(dtype).name, backend or _backend())


def qmm_key(k: int, n: int, dtype, backend: Optional[str] = None) -> Key:
    """Quantized matmul (kernels/q_matmul.py): x (T, k) @ W_q (k, n).
    ``dtype`` is the ACTIVATION dtype (codes are int8 by construction);
    ``Tuning.group_tile`` doubles as the out-channel tile here."""
    return (_op("qmm"), k, n, jnp.dtype(dtype).name, backend or _backend())


def gs_qmm_key(r: int, b: int, n: int, dtype,
               backend: Optional[str] = None) -> Key:
    """Fused rotate+quantized-matmul: GS factors (r, b, b), W_q (r*b, n)."""
    return (_op("gs_qmm"), r, b, n, jnp.dtype(dtype).name,
            backend or _backend())


def paged_attn_key(h: int, kh: int, d: int, page: int, dtype,
                   backend: Optional[str] = None) -> Key:
    """Paged decode attention (kernels/flash_attention.py): one query token
    per row gathered through a page table over the shared KV page pool.
    The launch geometry is fixed by (heads, page) — the key exists so the
    serving path resolves through the same registry (and the persisted
    tuning cache) as every other kernel."""
    return (_op("paged_attn"), h, kh, d, page, jnp.dtype(dtype).name,
            backend or _backend())


# Banked (per-request, multi-adapter) activation-side transforms resolve
# their launch geometry through these key families — keyed by KERNEL name
# (this module's vocabulary); WHICH family an adapter method rides is that
# method's ``MethodOps.banked_kernel`` field in core.methods (single
# source of per-method truth). Today: the gsoft bank rides the gs_T kernel
# ("gs"; vmapped per row, or row-batched at decode, which tunes nothing
# here: ``banked_gs_rows_tiles``), oft/boft banks ride the vmapped bdmm
# kernel ("bdmm",
# one bdmm per butterfly level for boft), and householder declares no
# kernel — its banked transform is an O(k*d)-per-token reference einsum
# (kernels/ref.py), so there is nothing to tune.
BANKED_KEYS: Dict[str, Callable] = {
    "gs": gs_key,
    "bdmm": bdmm_key,
}


def banked_key_fn(kernel: str) -> Optional[Callable]:
    """Key builder for a banked-transform kernel family (""/unknown ->
    einsum-only, nothing to tune — a new method starts on the reference
    fallback until a kernel lands)."""
    return BANKED_KEYS.get(kernel)


def _wildcard(key: Key) -> Key:
    return key[:-2] + ("*", "*")


def register_tuning(key: Key, tuning: Tuning) -> None:
    """Pin the launch geometry for a call-site key (highest precedence)."""
    _OVERRIDES[key] = tuning


def install_tunings(entries: Iterable[Tuple]) -> None:
    """Install config-level overrides (``ModelConfig.kernel_tunings``).

    Each entry is a tuple:
        ("bdmm",   r, bo, bi, token_tile, group_tile)
        ("gs",     r, b,      token_tile)
        ("qmm",    k, n,      token_tile, n_tile)
        ("gs_qmm", r, b, n,   token_tile, n_tile)
    Entries apply to every dtype/backend (wildcard keys). Each call replaces
    the previously installed config set.
    """
    for key in _CONFIG_KEYS:
        _OVERRIDES.pop(key, None)
    _CONFIG_KEYS.clear()
    for e in entries or ():
        op = e[0]
        if op == "bdmm":
            _, r, bo, bi, tt, gt = e
            key = _wildcard(bdmm_key(r, bo, bi, jnp.float32))
            tun = Tuning(token_tile=tt, group_tile=gt)
        elif op == "gs":
            _, r, b, tt = e
            key = _wildcard(gs_key(r, b, jnp.float32))
            tun = Tuning(token_tile=tt)
        elif op == "qmm":
            _, k, n, tt, nt = e
            key = _wildcard(qmm_key(k, n, jnp.float32))
            tun = Tuning(token_tile=tt, group_tile=nt)
        elif op == "gs_qmm":
            _, r, b, n, tt, nt = e
            key = _wildcard(gs_qmm_key(r, b, n, jnp.float32))
            tun = Tuning(token_tile=tt, group_tile=nt)
        else:
            raise ValueError(f"unknown kernel_tunings op {op!r}")
        register_tuning(key, tun)
        _CONFIG_KEYS.add(key)


def get_tuning(key: Key) -> Tuning:
    """Resolve launch geometry: override > wildcard override > autotuned >
    heuristic default."""
    _ensure_cache_loaded()
    if key in _OVERRIDES:
        return _OVERRIDES[key]
    wc = _wildcard(key)
    if wc in _OVERRIDES:
        return _OVERRIDES[wc]
    if key in _TUNED:
        return _TUNED[key]
    op = str(key[0]).split("@", 1)[0]     # strip any serve-TP tag
    if op == "bdmm":
        _, r, bo, bi = key[:4]
        return Tuning(token_tile=128, group_tile=default_group_tile(r, bi))
    if op == "qmm":
        return Tuning(token_tile=128, group_tile=default_n_tile(key[2]))
    if op == "gs_qmm":
        return Tuning(token_tile=128, group_tile=default_n_tile(key[3]))
    return Tuning(token_tile=128)


def clear_tunings() -> None:
    _OVERRIDES.clear()
    _TUNED.clear()
    _CONFIG_KEYS.clear()


# ---------------------------------------------------------------------------
# tuning-cache persistence: autotuned results survive the process
# ---------------------------------------------------------------------------
# Autotuning times real kernel launches, so re-deriving the same geometry
# every process is pure waste. ``save_tuning_cache`` serializes _TUNED to
# JSON keyed exactly like the in-memory registry ((op, *shape_sig, dtype,
# backend) tuples); ``load_tuning_cache`` restores entries WITHOUT clobbering
# results timed in this process, and config overrides still outrank both.
# Set ``REPRO_TUNING_CACHE=/path/cache.json`` to make the round trip
# automatic: lazily loaded on the first resolution, written through after
# every autotune_* call.

TUNING_CACHE_ENV = "REPRO_TUNING_CACHE"
_cache_loaded = False


def save_tuning_cache(path: Optional[str] = None) -> Optional[str]:
    """Write every autotuned entry to ``path`` (default: $REPRO_TUNING_CACHE;
    no-op returning None when neither names a file)."""
    path = path or os.environ.get(TUNING_CACHE_ENV)
    if not path:
        return None
    entries = [{"key": list(k), "token_tile": t.token_tile,
                "group_tile": t.group_tile}
               for k, t in sorted(_TUNED.items(),
                                  key=lambda kv: tuple(map(str, kv[0])))]
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=2)
    return path


def load_tuning_cache(path: Optional[str] = None) -> int:
    """Merge a saved cache into the autotuned tier (results timed in THIS
    process win ties; explicit overrides always outrank). Returns the number
    of entries loaded; missing/unset path -> 0."""
    path = path or os.environ.get(TUNING_CACHE_ENV)
    if not path or not os.path.exists(path):
        return 0
    with open(path) as f:
        data = json.load(f)
    n = 0
    for e in data.get("entries", ()):
        key = tuple(e["key"])
        if key not in _TUNED:
            _TUNED[key] = Tuning(token_tile=int(e["token_tile"]),
                                 group_tile=int(e.get("group_tile", 0)))
            n += 1
    return n


def _ensure_cache_loaded() -> None:
    global _cache_loaded
    if not _cache_loaded:
        _cache_loaded = True
        if os.environ.get(TUNING_CACHE_ENV):
            load_tuning_cache()


def _write_through() -> None:
    if os.environ.get(TUNING_CACHE_ENV):
        save_tuning_cache()


# Banked GS rotations (one adapter per row) take one of two kernels, chosen
# by the input's shape. The per-row path vmaps the token-major ``gs_T``
# kernel: each row gets its own grid of cdiv(T, token_tile) x r/group_tile
# steps, and at decode (T = 1) every step holds one token and a few blocks,
# so the launch is all per-step overhead. Below ROWS_MAX_TOKENS tokens per
# row (the crossover on a v5e at d 8192, b 32, 32 rows: the row-batched
# rotation took 0.66 / 0.90 / 1.30 / 2.09 ms at 1 / 2 / 4 / 8 tokens, the
# per-row one 1.55-1.61 ms at each), the row-batched kernel takes all rows
# in one launch with large steps:
# ROWS_GROUP_TILE blocks of the most rows (a power of two) whose blocks fit
# ROWS_STEP_VMEM, a (b, b) block taking whole (sublane, 128-lane) tiles in
# VMEM: 16 rows of 32 blocks of 32 x 32 bf16, 1 MB of blocks per step.
ROWS_MAX_TOKENS = 5
ROWS_GROUP_TILE = 32
ROWS_STEP_VMEM = 4 << 20


def banked_gs_rows_tiles(tokens: int, rows: int, r: int, b: int,
                         itemsize: int) -> Optional[Tuple[int, int]]:
    """(row_tile, group_tile) of the row-batched banked GS kernel for
    ``rows`` rows of ``tokens`` tokens over r blocks of b x b, or None where
    the per-row path runs (enough tokens per row to fill its token tiles)."""
    if tokens >= ROWS_MAX_TOKENS:
        return None
    sublanes = 32 // itemsize
    block = (-(-b // sublanes) * sublanes) * (-(-b // 128) * 128) * itemsize
    group_tile = min(r, ROWS_GROUP_TILE)
    fit = max(1, ROWS_STEP_VMEM // (group_tile * block))
    return min(rows, 1 << (fit.bit_length() - 1)), group_tile


def pick_chunk(t: int, chunk: int) -> int:
    """Largest divisor of t that is <= chunk (SSD scan chunking)."""
    q = min(chunk, t)
    while t % q:
        q -= 1
    return max(q, 1)


# ---------------------------------------------------------------------------
# autotuner (eager; results cached in the registry)
# ---------------------------------------------------------------------------

def _time_us(fn, *args, iters: int = 5, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def autotune_bdmm(r: int, bo: int, bi: int, t: int, dtype=jnp.float32, *,
                  token_tiles: Sequence[int] = DEFAULT_TOKEN_TILES,
                  group_tiles: Optional[Sequence[int]] = None,
                  iters: int = 5) -> Tuning:
    """Search (token_tile, group_tile) by timing real launches; cache best."""
    key = bdmm_key(r, bo, bi, dtype)
    _ensure_cache_loaded()
    if key in _TUNED:
        return _TUNED[key]
    if group_tiles is None:
        group_tiles = sorted({g for g in (1, 2, 4, 8, default_group_tile(r, bi))
                              if r % g == 0 and g <= r})
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    blocks = jax.random.normal(k1, (r, bo, bi), dtype)
    x = jax.random.normal(k2, (t, r * bi), dtype)
    interp = interpret_mode()
    best, best_us = None, float("inf")
    for tt in token_tiles:
        for gt in group_tiles:
            fn = jax.jit(functools.partial(
                bdmm_pallas, token_tile=tt, group_tile=gt, interpret=interp))
            us = _time_us(fn, blocks, x, iters=iters)
            if us < best_us:
                best, best_us = Tuning(token_tile=tt, group_tile=gt), us
    _TUNED[key] = best
    _write_through()
    return best


def autotune_gs(r: int, b: int, t: int, dtype=jnp.float32, *,
                token_tiles: Sequence[int] = DEFAULT_TOKEN_TILES,
                iters: int = 5) -> Tuning:
    key = gs_key(r, b, dtype)
    _ensure_cache_loaded()
    if key in _TUNED:
        return _TUNED[key]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    L = jax.random.normal(ks[0], (r, b, b), dtype)
    R = jax.random.normal(ks[1], (r, b, b), dtype)
    x = jax.random.normal(ks[2], (t, r * b), dtype)
    interp = interpret_mode()
    best, best_us = None, float("inf")
    for tt in token_tiles:
        fn = jax.jit(functools.partial(
            gs_fused_pallas, token_tile=tt, interpret=interp))
        us = _time_us(fn, L, R, x, iters=iters)
        if us < best_us:
            best, best_us = Tuning(token_tile=tt), us
    _TUNED[key] = best
    _write_through()
    return best


def autotune_qmm(k: int, n: int, t: int, dtype=jnp.bfloat16, *,
                 token_tiles: Sequence[int] = DEFAULT_TOKEN_TILES,
                 n_tiles: Optional[Sequence[int]] = None,
                 iters: int = 5) -> Tuning:
    """Search (token_tile, n_tile) for the quantized matmul; cache best.
    ``dtype`` is the activation dtype — codes are int8."""
    key = qmm_key(k, n, dtype)
    _ensure_cache_loaded()
    if key in _TUNED:
        return _TUNED[key]
    if n_tiles is None:
        n_tiles = sorted({nt for nt in (128, 256, 512, default_n_tile(n))
                          if n % nt == 0 and nt <= n} or {default_n_tile(n)})
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (t, k), dtype)
    q = jax.random.randint(k2, (k, n), -127, 128, jnp.int8)
    scale = jnp.full((1, n), 1e-2, jnp.float32)
    interp = interpret_mode()
    best, best_us = None, float("inf")
    for tt in token_tiles:
        for nt in n_tiles:
            fn = jax.jit(functools.partial(
                q_matmul_pallas, token_tile=tt, n_tile=nt, interpret=interp))
            us = _time_us(fn, x, q, scale, iters=iters)
            if us < best_us:
                best, best_us = Tuning(token_tile=tt, group_tile=nt), us
    _TUNED[key] = best
    _write_through()
    return best


# ---------------------------------------------------------------------------
# differentiable kernel entry points (2-D token-major inputs)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def bdmm_diff(tuning: Tuning, interpret: bool, blocks: Array,
              x: Array) -> Array:
    """Differentiable bdmm: blocks (r, bo, bi), x (T, r*bi) -> (T, r*bo)."""
    return bdmm_pallas(blocks, x, token_tile=tuning.token_tile,
                       group_tile=tuning.group_tile, interpret=interpret)


def _bdmm_fwd(tuning, interpret, blocks, x):
    y = bdmm_diff(tuning, interpret, blocks, x)
    return y, (blocks, x)


def _bdmm_bwd(tuning, interpret, res, dy):
    blocks, x = res
    r, bo, bi = blocks.shape
    dx = bdmm_pallas(jnp.swapaxes(blocks, -1, -2), dy,
                     token_tile=tuning.token_tile,
                     group_tile=tuning.group_tile, interpret=interpret)
    dblocks = bdmm_dblocks_pallas(dy, x, bo=bo, bi=bi,
                                  token_tile=tuning.token_tile,
                                  group_tile=tuning.group_tile,
                                  interpret=interpret)
    return dblocks.astype(blocks.dtype), dx.astype(x.dtype)


bdmm_diff.defvjp(_bdmm_fwd, _bdmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def gs_diff(tuning: Tuning, interpret: bool, L: Array, R: Array,
            x: Array) -> Array:
    """Differentiable GSOFT rotation  y = P^T L P R x."""
    return gs_fused_pallas(L, R, x, token_tile=tuning.token_tile,
                           interpret=interpret)


def _gs_fwd(tuning, interpret, L, R, x):
    y = gs_diff(tuning, interpret, L, R, x)
    return y, (L, R, x)


def _gs_bwd(tuning, interpret, res, dy):
    L, R, x = res
    dx, dL, dR = gs_fused_bwd_pallas(L, R, x, dy,
                                     token_tile=tuning.token_tile,
                                     interpret=interpret)
    return dL.astype(L.dtype), dR.astype(R.dtype), dx.astype(x.dtype)


gs_diff.defvjp(_gs_fwd, _gs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def gs_T_diff(tuning: Tuning, interpret: bool, L: Array, R: Array,
              x: Array) -> Array:
    """Differentiable transpose rotation  y = Q^T x = R^T P^T L^T P x."""
    return gs_fused_T_pallas(L, R, x, token_tile=tuning.token_tile,
                             interpret=interpret)


def _gs_T_fwd(tuning, interpret, L, R, x):
    y = gs_T_diff(tuning, interpret, L, R, x)
    return y, (L, R, x)


def _gs_T_bwd(tuning, interpret, res, dy):
    # <dy, Q^T x> = <x, Q dy>:  dx is the forward rotation of dy, and the
    # factor grads come from the grads-only backward kernel with (input,
    # cotangent) swapped.
    L, R, x = res
    dx = gs_fused_pallas(L, R, dy, token_tile=tuning.token_tile,
                         interpret=interpret)
    dL, dR = gs_fused_grads_pallas(L, R, dy, x,
                                   token_tile=tuning.token_tile,
                                   interpret=interpret)
    return dL.astype(L.dtype), dR.astype(R.dtype), dx.astype(x.dtype)


gs_T_diff.defvjp(_gs_T_fwd, _gs_T_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def gs_T_rows_diff(row_tile: int, group_tile: int, interpret: bool, L: Array,
                   R: Array, x: Array) -> Array:
    """Differentiable per-row transpose rotation for rows of few tokens:
    L, R (B, r, b, b), x (B, T, d). The backward runs the per-row kernels
    of ``gs_T_diff``."""
    return gs_fused_T_rows_pallas(L, R, x, row_tile=row_tile,
                                  group_tile=group_tile, interpret=interpret)


def _gs_T_rows_fwd(row_tile, group_tile, interpret, L, R, x):
    y = gs_T_rows_diff(row_tile, group_tile, interpret, L, R, x)
    return y, (L, R, x)


def _gs_T_rows_bwd(row_tile, group_tile, interpret, res, dy):
    L, R, x = res
    bwd = functools.partial(_gs_T_bwd, Tuning(), interpret)
    return jax.vmap(lambda l, rr, xx, g: bwd((l, rr, xx), g))(L, R, x, dy)


gs_T_rows_diff.defvjp(_gs_T_rows_fwd, _gs_T_rows_bwd)
