"""ModelRuntime — the single serving/eval entry point.

Binds ``ModelConfig + params + mesh (shard rules) + optional AdapterBank``
into one object that owns its jitted ``prefill`` / ``decode`` / ``loss``
closures, so engines, launchers, examples and benchmarks stop re-plumbing
``(cfg, params, mesh, bank, peft_cfg, adapter_ids, ...)`` through every
call. Per-request adapter state flows exclusively through
``AdapterContext`` pytrees built by ``runtime.context(slot_ids)``.

Adapters attach through ONE surface — ``runtime.attach(source)`` — which
accepts an ``AdapterStore`` (host-offloaded, LRU-paged under an HBM
budget), a pre-built eager ``AdapterBank``, named adapter trees + their
PEFTConfig(s), a checkpoint directory, or ``name=dir`` entry lists; the
serving side never touches raw checkpoint layout. The PR-5 trio
(``with_bank`` / ``save_bank`` / ``load_named_adapters``) survives as
warn-once deprecation shims over ``attach`` / ``repro.store``.

Serve-time tensor parallelism (ISSUE 8): building the runtime with a mesh
COMMITS its state onto it — params (quantized trees included) under the
Megatron column/row splits of ``sharding.specs``, contiguous and paged KV
state with kv-heads over the 'model' axis, eager bank factor stacks
replicated (or method-sharded via ``MethodOps.bank_shard_axes``). The
jitted prefill/slot-prefill/decode/chunk-prefill closures are built once
per geometry and GSPMD-partition against the committed input shardings,
so the serving engines run unchanged on 1..N devices; kernel dispatch
keys grow a ``tp`` tag so per-shard tunings never collide with
single-device ones.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import peft as peft_lib
from repro.models import api

Tree = Any

_deprecation_warned: set = set()


def _warn_once(old: str, new: str) -> None:
    """One DeprecationWarning per process per retired name (mirrors the
    PR-3 api-shim pattern; the names themselves go away next cycle)."""
    if old in _deprecation_warned:
        return
    _deprecation_warned.add(old)
    warnings.warn(f"{old} is deprecated; use {new}", DeprecationWarning,
                  stacklevel=3)


def _check_bank_quant_compatible(bank: peft_lib.AdapterBank) -> None:
    """Registry-driven capability gate: every method in the bank must be
    flagged ``quant_compatible`` (its rotations apply activation-side in
    bf16 BEFORE the int8 base matmul) to serve over quantized weights."""
    from repro.core import methods as methods_lib
    bad = [m for m in bank.bank_methods
           if not methods_lib.get(m).quant_compatible]
    if bad:
        raise ValueError(
            f"bank methods {bad} are not quantization-compatible — they "
            "cannot serve over quantized base weights (see the "
            "quant_compatible flag on their core.methods records)")


class ModelRuntime:
    """``ModelRuntime(cfg)`` initializes params; pass ``params=`` to reuse
    a tree. ``adapters``+``peft_cfg`` merge ONE adapter into the weights
    offline (the paper's zero-overhead static serving mode, §6.1); a
    ``bank`` serves per-request adapters activation-side. The two are
    mutually exclusive — merging and then rotating would apply adapters
    twice."""

    def __init__(self, cfg: ModelConfig, params: Optional[Tree] = None, *,
                 key: Optional[jax.Array] = None, mesh=None,
                 bank: Optional[peft_lib.AdapterBank] = None,
                 adapters: Optional[Tree] = None,
                 peft_cfg: Optional[peft_lib.PEFTConfig] = None,
                 abstract: bool = False):
        self.cfg = cfg
        self._ops = api.family_ops(cfg)      # fails fast on unknown family
        if params is None:
            params = (api.abstract_params(cfg) if abstract else
                      api.init_params(cfg, key if key is not None
                                      else jax.random.PRNGKey(0)))
        if adapters is not None:
            from repro import quant
            if quant.is_quantized_tree(params):
                raise ValueError(
                    "cannot merge adapters into already-quantized weights — "
                    "merge first, then call runtime.quantized() (quantizing "
                    "the merged tree keeps the rotation at full precision)")
        if (adapters is None) != (peft_cfg is None):
            raise ValueError(
                "offline merge needs BOTH adapters and peft_cfg — passing "
                "only one would silently serve the un-adapted base model")
        if adapters is not None and not adapters:
            raise ValueError(
                "empty adapter tree (target_patterns matched no weights?) — "
                "refusing a no-op merge that would silently serve the "
                "un-adapted base model")
        self._merged = adapters is not None
        if self._merged:
            if bank is not None:
                raise ValueError(
                    "pass EITHER merged adapters (adapters + peft_cfg) OR a "
                    "per-request bank — merging and then rotating per "
                    "request would apply adapters twice")
            params = peft_lib.materialize_tree(peft_cfg, params, adapters,
                                               merged=True)
        self.mesh = mesh
        if mesh is not None and not abstract and any(
                not isinstance(l, jax.ShapeDtypeStruct)
                for l in jax.tree.leaves(params)):
            from repro.sharding import specs as shard_specs
            rules = shard_specs.ShardingRules(cfg, mesh)
            params = shard_specs.place(mesh, params,
                                       rules.serve_params_tree(params))
            from repro.kernels import dispatch as kernel_dispatch
            kernel_dispatch.set_serve_tp(shard_specs.tp_size(mesh))
        self.params = params
        self.bank = bank
        self.quant_cfg = None        # set by .quantized() / load_quantized
        # jitted-closure cache. A plain dict (not attributes) so derived
        # runtimes (attach/detach — same cfg+mesh, closures take params as
        # arguments) can SHARE it by reference via ``_adopt_jit``: traces
        # land in the cache once, whichever runtime triggers them.
        self._jit: Dict[str, Any] = {"slot_prefill": {}}

    @classmethod
    def abstract(cls, cfg: ModelConfig, mesh=None) -> "ModelRuntime":
        """Runtime over ShapeDtypeStruct params (dry-run lowering)."""
        return cls(cfg, mesh=mesh, abstract=True)

    # -- adapter bank ---------------------------------------------------------
    @property
    def banked(self) -> bool:
        return self.bank is not None

    def slot(self, name: Optional[str]) -> int:
        """Bank slot id for an adapter name (0 = identity). Naming an
        adapter on a bankless runtime raises — silently serving the base
        model instead of the requested fine-tune is the failure mode this
        API exists to prevent."""
        if self.bank is None:
            if name is not None:
                raise KeyError(f"runtime has no adapter bank; cannot serve "
                               f"adapter {name!r} — attach one with "
                               "ModelRuntime.attach")
            return 0
        return self.bank.slot(name)

    def context(self, slot_ids) -> Optional[peft_lib.AdapterContext]:
        """AdapterContext binding the bank to a batch of slot ids
        (None when this runtime serves the bare/merged model)."""
        if self.bank is None:
            return None
        return self.bank.context(slot_ids)

    # -- residency surface (engines call these; trivial on eager banks) -------
    def validate_adapter(self, name: Optional[str]) -> None:
        """Submission-time check: the name must be servable (resident OR
        host-side). Unknown names raise listing both tiers; naming any
        adapter on a bankless runtime raises — silently serving the base
        model instead of the requested fine-tune is the failure mode this
        API exists to prevent."""
        if self.bank is None:
            if name is not None:
                raise KeyError(f"runtime has no adapter bank; cannot serve "
                               f"adapter {name!r} — attach one with "
                               "ModelRuntime.attach")
            return
        self.bank.validate(name)

    def acquire_adapter(self, name: Optional[str]) -> Optional[int]:
        """Admission-time slot claim (pins; may page in on a store-backed
        bank). None = admission stall: every slot of the adapter's method
        is pinned by in-flight requests — keep decoding and retry."""
        if self.bank is None:
            self.validate_adapter(name)
            return 0
        return self.bank.acquire(name)

    def release_adapter(self, name: Optional[str]) -> None:
        """Request-finished unpin (no-op on eager/bankless runtimes)."""
        if self.bank is not None:
            self.bank.release(name)

    def attach(self, source, peft_cfg: Optional["peft_lib.PEFTConfigs"] = None,
               *, hbm_budget: Optional[int] = None) -> "ModelRuntime":
        """New runtime over the same params serving per-request adapters
        (universal slot 0 stays the identity/base model). THE one adapter
        attachment surface; ``source`` may be:

          * an ``repro.store.AdapterStore`` — host-offloaded adapters,
            LRU-paged into a slot-compacted HBM bank sized by
            ``hbm_budget`` (default: everything resident, still compact);
          * a pre-built eager ``AdapterBank``;
          * ``{name: adapter_tree}`` + ``peft_cfg`` (a single PEFTConfig
            or a {name: PEFTConfig} mapping for mixed-method serving) —
            eager bank, unless ``hbm_budget`` is given (then store-paged);
          * a checkpoint directory (str) — opened as a DISK-backED store:
            only the index loads up front, adapters page in on admission;
          * a list of ``"name=ckpt_dir"`` / ``"ckpt_dir"`` entries (the
            launcher's ``--adapters`` form).
        """
        from repro import store as store_lib
        if self._merged:
            raise ValueError(
                "this runtime's params already contain a merged adapter; "
                "banking on top would rotate already-rotated activations — "
                "attach to the unmerged base runtime")
        if isinstance(source, (list, tuple)):
            if peft_cfg is not None:
                raise ValueError("checkpoint entries carry their own "
                                 "PEFTConfigs — do not pass peft_cfg")
            source, peft_cfg = store_lib.load_adapter_checkpoints(source)
        if isinstance(source, str):
            if peft_cfg is not None:
                raise ValueError("a checkpoint directory carries its own "
                                 "PEFTConfigs — do not pass peft_cfg")
            source = store_lib.AdapterStore.open(source)
        if isinstance(source, peft_lib.AdapterBank):
            if peft_cfg is not None or hbm_budget is not None:
                raise ValueError("a pre-built AdapterBank is attached "
                                 "as-is — peft_cfg/hbm_budget do not apply")
            bank = source
        elif isinstance(source, store_lib.AdapterStore):
            if peft_cfg is not None:
                raise ValueError("an AdapterStore carries its own "
                                 "PEFTConfigs — do not pass peft_cfg")
            bank = store_lib.PagedAdapterBank(source, self.params,
                                              hbm_budget=hbm_budget)
        elif isinstance(source, Mapping):
            if peft_cfg is None:
                raise ValueError(
                    "attach({name: adapters}) needs peft_cfg — a single "
                    "PEFTConfig or a {name: PEFTConfig} mapping")
            if hbm_budget is not None:
                bank = store_lib.PagedAdapterBank(
                    store_lib.AdapterStore.from_adapters(source, peft_cfg),
                    self.params, hbm_budget=hbm_budget)
            else:
                bank = peft_lib.build_adapter_bank(peft_cfg, self.params,
                                                   source)
        else:
            raise TypeError(f"cannot attach {type(source).__name__}: expected "
                            "AdapterStore, AdapterBank, {name: adapters}, a "
                            "checkpoint dir, or checkpoint entries")
        if self.is_quantized:
            _check_bank_quant_compatible(bank)
        if self.mesh is not None and isinstance(bank, peft_lib.AdapterBank):
            # eager bank: commit factor stacks onto the serve mesh
            # (replicated unless the method's bank_shard_axes hook opts a
            # factor axis into the 'model' split)
            from repro.sharding import specs as shard_specs
            rules = shard_specs.ShardingRules(self.cfg, self.mesh)
            bank.tree = shard_specs.place(self.mesh, bank.tree,
                                          rules.bank_spec_tree(bank.tree))
        elif self.mesh is not None:
            # store-paged bank: stacks replicated over the serve mesh; its
            # page-ins write in place and keep that placement
            bank.place(jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()))
        rt = ModelRuntime(self.cfg, self.params, mesh=self.mesh, bank=bank)
        rt.quant_cfg = self.quant_cfg   # quantize-then-bank commutes
        self._adopt_jit(rt)
        return rt

    def detach(self) -> "ModelRuntime":
        """New runtime over the same params with no adapter bank."""
        rt = ModelRuntime(self.cfg, self.params, mesh=self.mesh)
        rt.quant_cfg = self.quant_cfg
        rt._merged = self._merged
        self._adopt_jit(rt)
        return rt

    def _adopt_jit(self, other: "ModelRuntime") -> None:
        """Share the jitted-closure cache with a runtime derived from this
        one. attach/detach keep (cfg, mesh) and the closures take params /
        bank state as ARGUMENTS, so traces transfer; sharing by REFERENCE
        means every replica of an ``EngineCluster`` built via ``attach``
        reuses one compiled program set instead of re-tracing N times."""
        other._jit = self._jit

    def with_bank(self, adapters_by_name: Dict[str, Tree],
                  peft_cfg: "peft_lib.PEFTConfigs") -> "ModelRuntime":
        """Deprecated: use ``attach(adapters_by_name, peft_cfg)``."""
        _warn_once("ModelRuntime.with_bank", "ModelRuntime.attach")
        return self.attach(adapters_by_name, peft_cfg)

    # -- quantized serving ----------------------------------------------------
    @property
    def is_quantized(self) -> bool:
        return self.quant_cfg is not None

    def quantized(self, mode: Optional[str] = None, *,
                  qcfg=None) -> "ModelRuntime":
        """New runtime over the same model with base weights quantized for
        inference (per-output-channel symmetric int8 by default; fp8 stub
        behind a dtype gate). Pass ``mode`` OR a full ``qcfg`` — naming
        both only works when they agree. The adapter bank — when present —
        is carried over UNTOUCHED: GS rotations stay bf16 and apply
        activation-side before the quantized base matmuls (QOFT recipe,
        DESIGN.md)."""
        from repro import quant
        if self.is_quantized:
            raise ValueError("runtime is already quantized "
                             f"(mode={self.quant_cfg.mode!r})")
        if qcfg is None:
            qcfg = quant.QuantConfig(mode=mode or "int8",
                                     use_pallas=self.cfg.use_pallas)
        elif mode is not None and qcfg.mode != mode:
            raise ValueError(
                f"quantized(mode={mode!r}) conflicts with qcfg.mode="
                f"{qcfg.mode!r} — pass one or the other")
        if self.bank is not None:
            _check_bank_quant_compatible(self.bank)
        rt = ModelRuntime(self.cfg, quant.quantize_params(self.params, qcfg),
                          mesh=self.mesh, bank=self.bank)
        rt._merged = self._merged
        rt.quant_cfg = qcfg
        self._adopt_jit(rt)     # same traces cache; new avals re-specialize
        return rt

    @classmethod
    def load_quantized(cls, directory: str, cfg: ModelConfig, *,
                       qcfg=None, mesh=None, step: Optional[int] = None
                       ) -> "ModelRuntime":
        """Runtime from a checkpoint, served quantized.

        A quantized checkpoint (``CheckpointManager.save_quantized``)
        restores codes+scales directly with its saved QuantConfig (the
        kernel path follows ``cfg.use_pallas``/``qcfg`` — execution
        strategy is chosen at load time, not baked into the checkpoint);
        a plain float checkpoint is quantized ON LOAD with ``qcfg``
        (default int8) — the upgrade path for existing bf16 checkpoints."""
        from repro.checkpoint.manager import CheckpointManager
        qparams, used_cfg = CheckpointManager(directory).restore_quantized(
            api.abstract_params(cfg), qcfg=qcfg, step=step,
            use_pallas=cfg.use_pallas)
        rt = cls(cfg, qparams, mesh=mesh)
        rt.quant_cfg = used_cfg
        return rt

    # -- checkpoint integration (deprecated shims over repro.store) -----------
    @staticmethod
    def save_bank(directory: str, adapters_by_name: Dict[str, Tree],
                  peft_cfg: "peft_lib.PEFTConfigs", step: int = 0) -> None:
        """Deprecated: use ``repro.store.AdapterStore.from_adapters(...)
        .save(directory)`` (same on-disk format)."""
        _warn_once("ModelRuntime.save_bank",
                   "repro.store.AdapterStore.from_adapters(...).save(dir)")
        from repro.store import AdapterStore
        AdapterStore.from_adapters(adapters_by_name,
                                   peft_cfg).save(directory, step)

    @staticmethod
    def load_named_adapters(entries: List[str]
                            ) -> Tuple[Dict[str, Tree],
                                       "peft_lib.PEFTConfigs"]:
        """Deprecated: ``ModelRuntime.attach`` takes the entry list
        directly (or use ``repro.store.load_adapter_checkpoints``)."""
        _warn_once("ModelRuntime.load_named_adapters",
                   "ModelRuntime.attach(entries) / "
                   "repro.store.load_adapter_checkpoints")
        from repro.store import load_adapter_checkpoints
        return load_adapter_checkpoints(entries)

    # -- family ops / state ---------------------------------------------------
    @property
    def stateless(self) -> bool:
        """True for families with no token-level decode state (they serve
        whole inputs through ``infer_fn`` — e.g. ``image``)."""
        return self._ops.stateless

    def init_decode_state(self, batch: int, max_len: int, enc_len: int = 0):
        if self._ops.init_decode_state is None:
            raise ValueError(
                f"family {self.cfg.family!r} is stateless — it has no "
                "decode state; serve it through infer_fn / ImageServeEngine")
        return self._ops.init_decode_state(self.cfg, batch, max_len, enc_len)

    def decode_state(self, batch: int, max_len: int, enc_len: int = 0):
        """Contiguous decode state (one max_len KV region per slot). THE
        engine/bench-facing constructor — a grep guard keeps raw
        ``init_decode_state(`` calls confined to this module so every
        contiguous allocation is auditable against the paged path. On a
        meshed runtime the KV caches commit with kv-heads over 'model'."""
        state = self.init_decode_state(batch, max_len, enc_len)
        if self.mesh is not None:
            from repro.sharding import specs as shard_specs
            rules = shard_specs.ShardingRules(self.cfg, self.mesh)
            state = shard_specs.place(
                self.mesh, state, rules.decode_state_spec(state, batch))
        return state

    def paged_state(self, batch: int, num_pages: int, page_size: int,
                    max_pages: int):
        """Paged decode state: per-layer (num_pages, page_size, K, D) pools
        shared by all slots + a (batch, max_pages + 1) int32 page table per
        slot (sentinel garbage column last). Raises for families without a
        paged serve path. On a meshed runtime the page pools commit with
        kv-heads over 'model'; the table stays replicated (host-side page
        allocation never sees the mesh)."""
        if self._ops.init_paged_state is None:
            raise ValueError(f"family {self.cfg.family!r} has no paged "
                             "KV serve path")
        state = self._ops.init_paged_state(self.cfg, batch, num_pages,
                                           page_size, max_pages)
        if self.mesh is not None:
            from repro.sharding import specs as shard_specs
            rules = shard_specs.ShardingRules(self.cfg, self.mesh)
            state = shard_specs.place(self.mesh, state,
                                      rules.paged_state_spec(state))
        return state

    def active_param_count(self) -> int:
        return self._ops.active_param_count(self.cfg)

    # -- unjitted step builders (dry-run lowering with custom shardings) ------
    def build_prefill(self, batch_divisible: bool = True):
        from repro.train.steps import build_prefill_step
        return build_prefill_step(self.cfg, self.mesh, batch_divisible)

    def build_decode(self, batch_divisible: bool = True):
        from repro.train.steps import build_decode_step
        return build_decode_step(self.cfg, self.mesh, batch_divisible)

    # -- jitted closures (lazy, cached on the runtime) ------------------------
    def prefill_fn(self):
        """jitted (params, PrefillRequest, state) -> (logits, state)."""
        if self._jit.get("prefill") is None:
            self._jit["prefill"] = jax.jit(self.build_prefill())
        return self._jit["prefill"]

    def decode_fn(self):
        """jitted (params, ctx, tokens, state, pos) ->
        (next_tok, logits, state); ``state`` is donated."""
        if self._jit.get("decode") is None:
            self._jit["decode"] = jax.jit(self.build_decode(),
                                          donate_argnums=(3,))
        return self._jit["decode"]

    def paged_decode_fn(self):
        """jitted (params, ctx, tokens, state, pos) ->
        (next_tok, logits, state) through page tables; state donated."""
        if self._jit.get("paged_decode") is None:
            from repro.train.steps import build_paged_decode_step
            self._jit["paged_decode"] = jax.jit(
                build_paged_decode_step(self.cfg, self.mesh),
                donate_argnums=(3,))
        return self._jit["paged_decode"]

    def chunk_prefill_fn(self):
        """jitted (params, req, state, slot, start) -> (first,
        last_logits, state); state donated. One trace per chunk width (req
        token shape)."""
        if self._jit.get("chunk_prefill") is None:
            from repro.train.steps import build_chunk_prefill_step
            self._jit["chunk_prefill"] = jax.jit(
                build_chunk_prefill_step(self.cfg, self.mesh),
                donate_argnums=(2,))
        return self._jit["chunk_prefill"]

    def slot_prefill_fn(self, max_len: int, enc_len: int = 0):
        """jitted (params, PrefillRequest, state, slot) -> (first, state);
        ``state`` is donated. Cached per (max_len, enc_len) geometry."""
        key = (max_len, enc_len)
        cache = self._jit["slot_prefill"]
        if key not in cache:
            from repro.train.steps import build_slot_prefill_step
            cache[key] = jax.jit(
                build_slot_prefill_step(self.cfg, self.mesh, max_len=max_len,
                                        enc_len=enc_len),
                donate_argnums=(2,))
        return cache[key]

    def infer_fn(self):
        """jitted (params, ctx, inputs) -> logits — the STATELESS serving
        entry point (``FamilyOps.infer``): one whole-input batched forward,
        no KV. ``ctx`` is the same AdapterContext the decode path takes, so
        per-request banked adapters work identically."""
        if self._jit.get("infer") is None:
            if self._ops.infer is None:
                raise ValueError(
                    f"family {self.cfg.family!r} has no stateless infer "
                    "entry point — serve it through prefill/decode")
            cfg, shard = self.cfg, self._shard()
            fam = self._ops
            self._jit["infer"] = jax.jit(
                lambda params, ctx, inputs: fam.infer(cfg, params, inputs,
                                                      shard, ctx=ctx))
        return self._jit["infer"]

    def infer(self, inputs,
              ctx: Optional[peft_lib.AdapterContext] = None):
        return self.infer_fn()(self.params, ctx, inputs)

    def loss_fn(self):
        """jitted (params, batch) -> (loss, metrics)."""
        if self._jit.get("loss") is None:
            cfg, shard = self.cfg, self._shard()
            fam = self._ops
            self._jit["loss"] = jax.jit(
                lambda params, batch: fam.loss(cfg, params, batch, shard))
        return self._jit["loss"]

    def loss(self, batch):
        return self.loss_fn()(self.params, batch)

    def prefill(self, req: peft_lib.PrefillRequest, state):
        return self.prefill_fn()(self.params, req, state)

    def decode(self, tokens, state, pos,
               ctx: Optional[peft_lib.AdapterContext] = None):
        return self.decode_fn()(self.params, ctx, tokens, state, pos)

    def _shard(self):
        if self.mesh is None:
            from repro.models.layers import no_shard
            return no_shard
        from repro.sharding.specs import ShardingRules
        return ShardingRules(self.cfg, self.mesh).make_sharder()
