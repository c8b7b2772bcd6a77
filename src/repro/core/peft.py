"""PEFT engine — attaches adapters to arbitrary parameter trees.

The framework keeps *frozen* base params and *trainable* adapter params as
separate pytrees; the jitted train step calls ``materialize_tree`` to build
effective weights (differentiable w.r.t. adapters only), so:

  * optimizer state exists only for adapters (tiny),
  * base weights can live in bf16 with no master copies,
  * under tensor parallelism the GSOFT rotation adds **zero collectives**
    (Q acts on the unsharded input dim of each Megatron-sharded weight).

Adapted-weight selection is by path regex; weights with leading batch dims
(scan-stacked layers ``(L, d_in, d_out)``, MoE experts ``(L, E, d_in, d_out)``)
receive independent per-slice adapters via vmap.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Dict, List, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from . import methods as methods_lib
from .adapters import (AdapterSpec, apply_activation_side, init_adapter,
                       materialize)

Array = jnp.ndarray
Tree = Any

# weights the paper adapts: attention projections + MLP matrices (and the
# SSM in/out projections for the state-space architectures — see DESIGN §5)
DEFAULT_TARGETS: Tuple[str, ...] = (
    r".*/(wq|wk|wv|wo|wi|wg)$",       # attention + MLP/MoE projections
    r".*/(wz|wx)$",                   # mamba in-projections (z / x branches)
    r".*/(in_proj|out_proj)$",
    r".*/wc$",                        # image-family conv channel mixers
)


@dataclasses.dataclass(frozen=True)
class PEFTConfig:
    method: str = "gsoft"          # any core.methods entry, or full|none
    block_size: int = 32
    block_size_out: int = 0
    rank: int = 8
    alpha: float = 16.0
    boft_factors: int = 2
    reflections: int = 4           # householder factor count (even)
    givens_rounds: int = 4         # givens brick-wall round count
    neumann_order: Optional[int] = None
    use_scale: bool = False
    use_pallas: bool = False       # GS rotations via the Pallas kernel path
    target_patterns: Tuple[str, ...] = DEFAULT_TARGETS

    @property
    def is_peft(self) -> bool:
        return methods_lib.is_adapter_method(self.method)


# ---------------------------------------------------------------------------
# path utilities
# ---------------------------------------------------------------------------

def _key_name(entry) -> str:
    if hasattr(entry, "key"):
        return str(entry.key)
    if hasattr(entry, "idx"):
        return str(entry.idx)
    if hasattr(entry, "name"):
        return str(entry.name)
    return str(entry)


def path_str(path) -> str:
    return "/".join(_key_name(p) for p in path)


def flatten_paths(tree: Tree, is_leaf=None) -> Dict[str, Array]:
    leaves = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return {path_str(p): v for p, v in leaves}


def matches_patterns(patterns, path: str) -> bool:
    """fullmatch only: an unanchored target like ``.*/wq`` must not also
    match a decoy weight named ``.../wq_extra`` (the old ``re.search``
    fallback ignored the end anchor). THE one implementation of
    target-pattern semantics — PEFT adapter selection and
    ``quant.weights`` both use it."""
    return any(re.fullmatch(pat, path) for pat in patterns)


def _matches(cfg: PEFTConfig, path: str) -> bool:
    return matches_patterns(cfg.target_patterns, path)


# ---------------------------------------------------------------------------
# spec inference + init
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def spec_for(cfg: PEFTConfig, shape: Tuple[int, ...]) -> AdapterSpec:
    """Derive the AdapterSpec for a weight shape. Cached: ``materialize_tree``
    runs inside jit every step and would otherwise re-derive the spec for
    every adapted leaf on every call (cfg and shape are both hashable)."""
    if len(shape) < 2:
        raise ValueError(f"cannot adapt weight of shape {shape}")
    return AdapterSpec(
        method=cfg.method,
        d_in=int(shape[-2]),
        d_out=int(shape[-1]),
        block_size=cfg.block_size,
        block_size_out=cfg.block_size_out,
        rank=cfg.rank,
        alpha=cfg.alpha,
        boft_factors=cfg.boft_factors,
        reflections=cfg.reflections,
        givens_rounds=cfg.givens_rounds,
        neumann_order=cfg.neumann_order,
        use_scale=cfg.use_scale,
        use_pallas=cfg.use_pallas,
        batch=tuple(int(s) for s in shape[:-2]),
    )


def adapted_paths(cfg: PEFTConfig, params: Tree) -> Dict[str, AdapterSpec]:
    """Which weights get adapters, and with what spec.

    Quantized trees work too: a ``QuantTensor`` stays ONE leaf here (its
    ``shape``/``ndim`` mirror the logical weight), so an adapter bank can
    be built over an already-quantized runtime — the adapters themselves
    are always full-precision, applied activation-side.
    """
    if not cfg.is_peft:
        return {}
    from repro.quant.core import is_quant_tensor
    out = {}
    for path, leaf in flatten_paths(params, is_leaf=is_quant_tensor).items():
        if leaf.ndim >= 2 and _matches(cfg, path):
            out[path] = spec_for(cfg, tuple(leaf.shape))
    return out


def init_peft(cfg: PEFTConfig, params: Tree, key: jax.Array,
              dtype=jnp.float32) -> Dict[str, Dict[str, Array]]:
    """Adapter tree: {weight_path: adapter_params}. Empty for full/none."""
    specs = adapted_paths(cfg, params)
    adapters: Dict[str, Dict[str, Array]] = {}
    for i, (path, spec) in enumerate(sorted(specs.items())):
        adapters[path] = init_adapter(spec, jax.random.fold_in(key, i), dtype)
    return adapters


# ---------------------------------------------------------------------------
# materialization / merge
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class AdaptedWeight:
    """A frozen weight and its adapter, applied where a layer uses it.

    ``materialize_tree(..., lazy=True)`` puts these in place of the weights
    the layers consume through ``models.layers.qlinear``, which calls
    ``apply``: input-rotation methods rotate the activations, x (Q W) =
    (x Q) W, so no adapted weight is ever built; other methods build W_eff
    right before the matmul. Inside the model's layer scan the children
    are the current layer's slices, so at most one layer's adapted weights
    exist at a time. Mirrors the weight's ``shape``/``ndim``/``dtype`` like
    ``QuantTensor`` does.
    """
    w: Array
    adapter: Dict[str, Array]
    cfg: PEFTConfig

    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("w"), self.w),
                 (jax.tree_util.GetAttrKey("adapter"), self.adapter)),
                self.cfg)

    @classmethod
    def tree_unflatten(cls, cfg, children):
        return cls(w=children[0], adapter=children[1], cfg=cfg)

    @property
    def shape(self):
        return self.w.shape

    @property
    def ndim(self) -> int:
        return self.w.ndim

    @property
    def dtype(self):
        return self.w.dtype

    def apply(self, x: Array) -> Tuple[Array, Array]:
        """(x', W') with x' @ W' == x @ W_eff."""
        spec = spec_for(self.cfg, tuple(self.w.shape))
        if (methods_lib.get(spec.method).apply_activation_side is None
                or spec.use_scale):
            return x, materialize(spec, self.adapter, self.w)
        return apply_activation_side(spec, self.adapter, x).astype(
            x.dtype), self.w


def materialize_tree(cfg: PEFTConfig, params: Tree,
                     adapters: Dict[str, Dict[str, Array]],
                     merged: bool = False, lazy: bool = False) -> Tree:
    """Effective parameter tree with adapters applied (weight-side unless
    ``lazy``). Weight-side cost is O(2 b d n) per adapted weight — a ~b/T
    fraction of the corresponding GEMM for T tokens (DESIGN §3).

    ``lazy=True`` (the train step) defers every weight the layers consume
    through ``qlinear`` to the point of use as an ``AdaptedWeight``;
    weights consumed elsewhere (expert stacks, SSM projections) are
    materialized here as before.

    ``merged=True`` documents the offline single-merge call sites (serving:
    adapters folded into the weights once, zero per-token overhead — paper
    §6.1). The math is identical; the flag only marks intent where the old
    ``merge_tree`` alias used to.
    """
    del merged  # intent marker only — same math either way
    if not adapters:
        return params
    from repro.quant.weights import DEFAULT_QUANT_TARGETS as QLINEAR_WEIGHTS

    def visit(path, leaf):
        p = path_str(path)
        if p not in adapters:
            return leaf
        if lazy and matches_patterns(QLINEAR_WEIGHTS, p):
            return AdaptedWeight(leaf, adapters[p], cfg)
        return _materialize_leaf(spec_for(cfg, leaf.shape), adapters[p],
                                 leaf)

    return jax.tree_util.tree_map_with_path(visit, params)


# One compiled program per adapted weight: called outside jit (the offline
# merge), XLA then bounds the rotation's temporaries to that weight instead
# of holding every eager intermediate. Inside jit it simply inlines.
_materialize_leaf = jax.jit(materialize, static_argnums=0)


# ---------------------------------------------------------------------------
# adapter bank: N named adapters + identity slot, per-request serving.
# Heterogeneous: each named adapter declares its own (registered, bankable)
# method; the identity slot stays universal.
# ---------------------------------------------------------------------------

BASE_ADAPTER = "__base__"

PEFTConfigs = Union[PEFTConfig, Mapping[str, PEFTConfig]]


@dataclasses.dataclass
class AdapterBank:
    """Stacked per-request orthogonal rotations for multi-adapter serving.

    ``tree`` mirrors the params nesting: each adapted weight path maps to
    ``{method: factors}`` where ``factors`` are that method's PRE-PROCESSED
    per-slot stacks (Cayley-orthogonalized GS/OFT/BOFT blocks, normalized
    Householder vectors — ``MethodOps.bank_build``; adapters are frozen
    when serving). Slot 0 is the identity (serves the unmodified base
    model); slots 1..N are the named adapters in ``names`` order. In a
    MIXED-method bank every method stack spans all A slots, holding that
    method's identity wherever the slot's adapter uses a different method —
    so slot ids stay universal and the per-row composition of all method
    stacks equals exactly the one non-identity rotation. Scan-stacked
    layer dims stay LEADING (before the A axis) so the model's layer scan
    slices the bank alongside the weights.

    The serving engine applies the bank activation-side — row i of a decode
    batch computes x_i Q_{ids[i]} before each adapted matmul, costing
    O(b*d) per token per weight versus O(d^2) to re-merge a dense rotation;
    that asymmetry is what makes per-request orthogonal adapters viable at
    continuous-batching granularity.
    """
    cfg: PEFTConfig                  # primary/default config (bank knobs)
    names: Tuple[str, ...]           # names[0] == BASE_ADAPTER
    tree: Dict[str, Any]
    # per-adapter configs (adapter names only; absent names use ``cfg``)
    cfgs: Dict[str, PEFTConfig] = dataclasses.field(default_factory=dict)

    @property
    def num_slots(self) -> int:
        return len(self.names)

    @property
    def bank_methods(self) -> Tuple[str, ...]:
        """Methods actually present in this bank (sorted)."""
        return tuple(sorted({c.method for c in self.cfgs.values()}))

    def cfg_for(self, name: str) -> PEFTConfig:
        """The PEFTConfig a named adapter was built with."""
        return self.cfgs.get(name, self.cfg)

    def slot(self, name: Optional[str]) -> int:
        """Bank slot for an adapter name (None / BASE_ADAPTER -> identity)."""
        if name is None:
            return 0
        try:
            return self.names.index(name)
        except ValueError:
            raise KeyError(f"unknown adapter '{name}'; bank has "
                           f"{list(self.names)}") from None

    def context(self, slot_ids) -> "AdapterContext":
        """Bind this bank to a batch of slot ids -> the per-request
        AdapterContext that flows through prefill/decode as ONE pytree."""
        return AdapterContext(bank=self.tree,
                              slots=jnp.asarray(slot_ids, jnp.int32),
                              peft=self.cfg)

    # -- residency surface (trivial here; real on the paged store bank) ------
    def validate(self, name: Optional[str]) -> None:
        """Raise KeyError on an unknown adapter name (None = identity)."""
        self.slot(name)

    def acquire(self, name: Optional[str]) -> Optional[int]:
        """Admission-time slot claim. An eager bank is always fully
        resident, so this is just the slot lookup; the paged store bank
        overrides it with page-in + pinning (may return None = stall)."""
        return self.slot(name)

    def release(self, name: Optional[str]) -> None:
        """Request-finished unpin (no-op for a fully-resident bank)."""


def _nest_insert(root: Dict[str, Any], path: str, value: Any) -> None:
    parts = path.split("/")
    node = root
    for seg in parts[:-1]:
        node = node.setdefault(seg, {})
    node[parts[-1]] = value


def normalize_bank_cfgs(adapters_by_name: Mapping[str, Any],
                        peft_cfg: PEFTConfigs
                        ) -> Tuple[PEFTConfig, Dict[str, PEFTConfig]]:
    """(primary, {name: cfg}) from either a single PEFTConfig (homogeneous
    bank) or a {name: PEFTConfig} mapping (mixed-method bank)."""
    if isinstance(peft_cfg, PEFTConfig):
        return peft_cfg, {name: peft_cfg for name in adapters_by_name}
    cfgs = dict(peft_cfg)
    missing = sorted(set(adapters_by_name) - set(cfgs))
    if missing:
        raise ValueError(f"no PEFTConfig for adapters {missing} — a mixed-"
                         "method bank needs one config per adapter name")
    if not cfgs:
        raise ValueError("empty PEFTConfig mapping — pass a single "
                         "PEFTConfig for an adapterless (identity-only) "
                         "bank")
    primary = next(iter(cfgs.values()))
    return primary, {name: cfgs[name] for name in adapters_by_name}


def bank_capability_check(name: Optional[str], cfg: PEFTConfig) -> None:
    """Registry-driven: the method must be registered AND provide
    ``bank_build`` (``MethodOps.bank_unsupported`` explains why not).
    Shared by eager bank builds AND ``AdapterStore.add`` — a host store
    fails at INSERT time, never at first admission mid-traffic."""
    ops = methods_lib.get(cfg.method)   # KeyError lists registered methods
    if ops.bank_build is None:
        who = f"adapter '{name}'" if name else "the bank config"
        raise ValueError(f"adapter bank cannot serve {who}: method "
                         f"{cfg.method!r} has no bank path — "
                         f"{ops.bank_unsupported}")
    if cfg.use_scale:
        raise ValueError("adapter bank does not support use_scale "
                         "(the per-output magnitude acts on the weight "
                         "output, not the rotated input)")


def check_bank_member(name: str, cfg: PEFTConfig, primary: PEFTConfig,
                      cfg_of_method: Dict[str, PEFTConfig]) -> None:
    """One adapter's admissibility against a bank/store under ``primary``:
    bankable method, bank-wide knobs, one config per method.

    Mutates ``cfg_of_method`` (method -> canonical config). THE shared
    membership rule — ``build_adapter_bank`` applies it when stacking
    up-front, ``repro.store.AdapterStore.add`` applies it at host-insert
    time so a bad adapter is rejected before it can break an admission."""
    bank_capability_check(name, cfg)
    if cfg.target_patterns != primary.target_patterns:
        raise ValueError(
            f"adapter '{name}': target_patterns differ from the bank's "
            "— all adapters in one bank must adapt the same weights")
    if cfg.use_pallas != primary.use_pallas:
        raise ValueError(
            f"adapter '{name}': use_pallas differs from the bank's — "
            "the kernel path is a bank-wide choice")
    prev = cfg_of_method.setdefault(cfg.method, cfg)
    if prev != cfg:
        raise ValueError(
            f"adapter '{name}' shares method {cfg.method!r} with other "
            "adapters but differs in config — one bank holds one stack "
            "(hence one config) per method")


def bank_specs(cfg: PEFTConfig, params: Tree) -> Dict[str, AdapterSpec]:
    """Adapted-path specs a serving bank can actually hold (rejects MoE /
    hybrid multi-batch-dim weights) — shared by the eager ``AdapterBank``
    build and the paged ``repro.store`` bank, so both fail identically."""
    specs = adapted_paths(cfg, params)
    for path, spec in specs.items():
        if len(spec.batch) > 1:
            raise ValueError(
                f"adapter bank cannot serve {path}: weights with batch dims "
                f"{spec.batch} (MoE experts / hybrid blocks) need "
                "routing-aware rotation")
    return specs


def build_adapter_bank(cfg: PEFTConfigs, params: Tree,
                       adapters_by_name: Dict[str, Dict[str, Dict[str, Array]]]
                       ) -> AdapterBank:
    """Build an AdapterBank from named adapter trees (as from ``init_peft``).

    ``cfg`` is a single PEFTConfig (every adapter uses it) or a
    {name: PEFTConfig} mapping for MIXED-method banks. Capability checks
    come from the ``core.methods`` registry: any method providing
    ``bank_build`` can be banked; per path, each method's factors are
    pre-processed up front and stacked over [identity] + adapters along a
    new A axis placed after any scan-stacked weight batch dims (slots of a
    different method hold that method's identity).

    Constraints: all configs must share ``target_patterns`` / ``use_pallas``
    (they define the bank-wide adapted set and kernel path), and adapters
    sharing a method must share its full config (one stack per method).
    """
    primary, cfg_by_name = normalize_bank_cfgs(adapters_by_name, cfg)
    bank_capability_check(None, primary)
    # one stack per method -> same-method adapters must share their config
    cfg_of_method: Dict[str, PEFTConfig] = {}
    names_of_method: Dict[str, set] = {}
    for name, c in cfg_by_name.items():
        check_bank_member(name, c, primary, cfg_of_method)
        names_of_method.setdefault(c.method, set()).add(name)

    specs = bank_specs(primary, params)
    names = (BASE_ADAPTER,) + tuple(adapters_by_name)
    tree: Dict[str, Any] = {}
    for path, spec in sorted(specs.items()):
        shape = tuple(spec.batch) + (spec.d_in, spec.d_out)
        entry: Dict[str, Any] = {}
        for m in sorted(cfg_of_method):
            mcfg = cfg_of_method[m]
            mspec = spec_for(mcfg, shape)
            members = names_of_method[m]
            params_by_slot: List[Optional[Dict[str, Array]]] = [None]
            for name in names[1:]:
                if name not in members:
                    params_by_slot.append(None)     # other method: identity
                    continue
                if path not in adapters_by_name[name]:
                    raise KeyError(
                        f"adapter '{name}' has no params for {path}")
                params_by_slot.append(adapters_by_name[name][path])
            entry[m] = methods_lib.get(m).bank_build(mspec, params_by_slot)
        _nest_insert(tree, path, entry)
    return AdapterBank(cfg=primary, names=names, tree=tree,
                       cfgs=cfg_by_name)


# ---------------------------------------------------------------------------
# adapter context: the ONE pytree that carries per-request adapter state
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class AdapterContext:
    """Per-request adapter state as a single frozen pytree.

    Bundles the stacked bank subtree (``AdapterBank.tree``), the (B,) slot
    ids of the current batch, and the bank's PEFTConfig — replacing the old
    loose ``bank``/``adapter_ids``/``bank_cfg`` kwarg triple. ``bank`` and
    ``slots`` are pytree children (they trace through jit/scan); ``peft`` is
    static aux data (hashable frozen dataclass, part of the jit cache key).

    ``slots`` is either ONE (B,) int32 array indexing every method stack
    (the eager padded ``AdapterBank``, where slot ids are universal because
    each stack holds identities at other methods' slots) or a
    ``{method: (B,) int32}`` dict of per-method COMPACT ids (the paged
    ``repro.store`` bank, whose stacks hold no identity padding — the
    host-side indirection table resolves universal slot -> compact slot
    per method before the context is built, so the device graph is
    identical either way: one gather per method stack).
    """
    bank: Tree                       # nested {path: {"L": ..., "R": ...}}
    slots: Array                     # (B,) int32 ids, or {method: (B,) ids}
    peft: Optional[PEFTConfig] = None

    def tree_flatten(self):
        return (self.bank, self.slots), self.peft

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(bank=children[0], slots=children[1], peft=aux)

    def group(self, *names) -> Optional[Dict]:
        """Bank subtree under ``names`` (e.g. ``"layers"``), or None.

        The returned raw tree is what model code feeds to ``jax.lax.scan``
        alongside the stacked layer weights — scan-slicing and the rotation
        hook below live in one place."""
        node: Any = self.bank
        for n in names:
            node = node.get(n) if isinstance(node, dict) else None
            if node is None:
                return None
        return node or None

    def rotator(self, group: Optional[Dict]) -> Optional["BankRotator"]:
        """Rotation hook over one (scan-sliced) module subtree, e.g.
        ``{"wq": {"L": (A, r, b, b), "R": ...}, ...}``. Returns None when
        there is nothing to rotate, so model code can pass it straight
        through to attention_block/apply_mlp."""
        if group is None or self.slots is None:
            return None
        return BankRotator(group, self.slots, self.peft)


class BankRotator:
    """Per-request rotation hook: ``rot(name, x)`` rotates row i of x with
    its own adapter (slot 0 = identity) before projection ``name``.

    Method-generic: a bank entry is ``{method: factors}`` and each method's
    ``MethodOps.bank_rotator`` applies its stack in turn. In a mixed bank
    at most one stack is non-identity for any given row, so the composition
    order is immaterial — it is fixed (sorted) only for trace stability.

    Besides being callable, it exposes ``quant_rotation`` so the
    ``qlinear`` hook can fuse the GS rotation + quantized base matmul into
    one ``gs_q_matmul_banked`` call instead of round-tripping the rotated
    slab through HBM. All factors are gathered/cast to the ACTIVATION
    dtype: rotations stay bf16 for EVERY method even when the base weights
    are int8 (QOFT rationale, DESIGN.md)."""

    __slots__ = ("_group", "slots", "_peft")

    def __init__(self, group: Dict, slots: Array,
                 peft: Optional[PEFTConfig]):
        self._group = group
        self.slots = slots
        self._peft = peft

    @property
    def use_pallas(self) -> bool:
        return self._peft.use_pallas if self._peft else False

    def _ids(self, method: str) -> Array:
        """Per-row ids into ``method``'s stack: universal slot ids index
        every stack of a padded bank; a slot-compacted store bank carries
        per-method compact ids (``AdapterContext.slots`` as a dict)."""
        if isinstance(self.slots, dict):
            return self.slots[method]
        return self.slots

    def __call__(self, name: str, x: Array) -> Array:
        entry = self._group.get(name)
        if entry is None:
            return x
        for m in sorted(entry):
            x = methods_lib.get(m).bank_rotator(entry[m], self._ids(m), x,
                                                self.use_pallas)
        return x

    def quant_rotation(self, name: str, x: Array, dtype
                       ) -> Tuple[Array, Optional[Tuple[Array, ...]]]:
        """Split the rotation for a QUANTIZED base matmul: apply every
        method stack that cannot fuse with the quantized kernel, and
        return the per-row factors of the (at most one) method that can
        (``MethodOps.quant_fuse`` — GSOFT's (L, R) today).

        -> (x with unfusible rotations applied, fusible factors or None).
        """
        entry = self._group.get(name)
        if entry is None:
            return x, None
        fused = None
        for m in sorted(entry):
            ops = methods_lib.get(m)
            if fused is None and ops.quant_fuse is not None:
                fused = ops.quant_fuse(entry[m], self._ids(m), dtype)
            else:
                x = ops.bank_rotator(entry[m], self._ids(m), x,
                                     self.use_pallas)
        return x, fused


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PrefillRequest:
    """Everything one prefill call needs beyond params/state, as a pytree:
    the input batch, the per-row ``last_idx`` (index of each row's last
    valid prompt position — the ragged-prompt fix), and the optional
    AdapterContext. Folds the old ``last_idx`` special-case kwarg and the
    adapter triple into one argument."""
    batch: Dict[str, Array]
    last_idx: Optional[Array] = None
    ctx: Optional[AdapterContext] = None

    def tree_flatten(self):
        return (self.batch, self.last_idx, self.ctx), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(batch=children[0], last_idx=children[1], ctx=children[2])


def count_params(tree: Tree) -> int:
    return sum(int(v.size) for v in jax.tree_util.tree_leaves(tree))


def trainable_and_frozen(cfg: PEFTConfig, params: Tree, adapters: Tree):
    """(trainable, frozen) split for the optimizer/train step (the
    ``full``/``none`` pseudo-methods are interpreted by the registry
    module — the one place method strings are compared)."""
    return methods_lib.trainable_split(cfg.method, params, adapters)
