"""PagedAdapterBank — fixed-HBM-budget view over AdapterStore pages.

The eager ``AdapterBank`` pre-builds every adapter into HBM and pads each
method's stack with identities at every OTHER method's slots, so resident
bytes scale O(N_adapters x N_methods). This bank fixes both axes:

  slot compaction   Each method's stack holds ONLY its own members:
                    shape ``(batch..., c_m + 1, ...)`` where ``c_m`` is
                    that method's share of the HBM budget and compact
                    slot 0 is the method identity. Universal slot ids
                    (0 = base, 1..capacity) survive unchanged — a host
                    indirection table per method maps universal slot ->
                    compact slot (0 where the slot's adapter uses a
                    different method), and ``context()`` resolves it into
                    the per-method ``{method: (B,) ids}`` dict that
                    ``BankRotator`` gathers with. The device graph is
                    identical to the padded bank: one gather per stack.

  LRU paging        Adapters page in at admission: factors come from the
                    host page cache (an evict->re-admit round trip never
                    re-runs ``bank_build``) or are built on the spot via
                    ``MethodOps.bank_build`` from the store's raw params,
                    then written into the method stack at the claimed
                    compact slot. Victims are the least-recently-admitted
                    UNPINNED members of the same method region; active
                    requests pin their adapter, so ``acquire`` returns
                    None (admission stall) rather than evicting a page a
                    resident slot is still decoding with — a full bank
                    never blocks decode of resident slots.

Stack shapes are fixed at construction (jit traces once; page-in swaps
array CONTENTS at unchanged shapes, so no retrace ever happens under
traffic). That is also why the per-method capacities ``c_m`` are static —
a hot method cannot borrow slots from a cold one mid-flight, because
borrowing would resize a stack and retrace every jitted step.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import methods as methods_lib
from repro.core import peft as peft_lib
from repro.obs.metrics import REGISTRY

from .store import AdapterStore

Tree = Any

#: reservoir size for the page-in latency histogram. The pre-obs bank kept
#: an append-forever ``page_in_ms`` LIST, which grew one float per miss for
#: the life of the process — a real leak under thousand-tenant churn. A
#: bounded reservoir keeps the p50/p95 queries and constant memory.
PAGE_IN_HIST_CAP = 1024


def split_budget(budget: int, counts: Dict[str, int]) -> Dict[str, int]:
    """Per-method compact capacities: proportional to store population,
    at least 1 each, never more than the method has members. Deterministic
    (ties break on method name)."""
    methods = sorted(counts)
    if not methods:
        return {}
    if budget < len(methods):
        raise ValueError(
            f"hbm_budget={budget} cannot hold one adapter per method — the "
            f"store mixes {len(methods)} methods ({methods})")
    caps = {m: 1 for m in methods}
    remaining = budget - len(methods)
    while remaining > 0:
        # most under-served method relative to its population, name-tied
        open_m = [m for m in methods if caps[m] < counts[m]]
        if not open_m:
            break
        pick = max(open_m, key=lambda m: (counts[m] / caps[m], m))
        caps[pick] += 1
        remaining -= 1
    return caps


class PagedAdapterBank:
    """LRU-paged, slot-compacted HBM bank over an ``AdapterStore``.

    Duck-types the ``AdapterBank`` serving surface (``context`` /
    ``validate`` / ``acquire`` / ``release`` / ``bank_methods`` / ``cfg``)
    so ``ModelRuntime`` and ``ServeEngine`` drive either interchangeably.
    """

    def __init__(self, store: AdapterStore, params: Tree, *,
                 hbm_budget: Optional[int] = None):
        self.store = store
        counts = store.method_counts()
        if hbm_budget is None:
            hbm_budget = max(len(store), 1)     # everything fits; still compact
        self.caps = split_budget(hbm_budget, counts)
        self.capacity = sum(self.caps.values())     # universal slots 1..cap
        self._methods: Tuple[str, ...] = tuple(sorted(self.caps))
        self.cfg = store.primary_cfg
        self._specs = peft_lib.bank_specs(self.cfg, params)

        # device stacks: {path: {method: {factor: (batch.., c_m+1, ...)}}}
        # _stacks[path][m] is the SAME dict object nested into self.tree,
        # so in-place page writes flow into every context built afterwards.
        self._stacks: Dict[str, Dict[str, Dict[str, jnp.ndarray]]] = {}
        self.tree: Dict[str, Any] = {}
        # per-path A-axis index: the slot axis sits after any scan-stacked
        # weight batch dims, which differ per weight, not per method
        self._axis: Dict[str, int] = {}
        for path, spec in sorted(self._specs.items()):
            shape = tuple(spec.batch) + (spec.d_in, spec.d_out)
            self._axis[path] = len(spec.batch)
            entry: Dict[str, Dict[str, jnp.ndarray]] = {}
            for m in self._methods:
                mspec = peft_lib.spec_for(store.cfg_of_method(m), shape)
                entry[m] = methods_lib.get(m).bank_build(
                    mspec, [None] * (self.caps[m] + 1))   # all-identity
            self._stacks[path] = entry
            peft_lib._nest_insert(self.tree, path, entry)

        # host indirection: universal slot -> compact slot, per method
        self._lut: Dict[str, np.ndarray] = {
            m: np.zeros(self.capacity + 1, np.int32) for m in self._methods}
        # residency: name -> (universal slot, method, compact slot)
        self._resident: Dict[str, Tuple[int, str, int]] = {}
        self._lru: Dict[str, None] = {}             # insertion-ordered
        self._pins: Dict[str, int] = {}
        self._free_universal: List[int] = list(range(self.capacity, 0, -1))
        self._free_compact: Dict[str, List[int]] = {
            m: list(range(self.caps[m], 0, -1)) for m in self._methods}
        # built factor pages on host — evict->re-admit skips bank_build
        self._page_cache: Dict[str, Dict[str, Dict[str, np.ndarray]]] = {}
        # instruments in the process metrics plane; `counters` (property)
        # and `stats()` are views. page_in_ms is a BOUNDED histogram now.
        scope = REGISTRY.scope("bank")
        self._c = scope.counters("hits", "misses", "evictions", "stalls",
                                 "builds", "build_cache_hits")
        self._page_in_ms = scope.histogram("page_in_ms",
                                           cap=PAGE_IN_HIST_CAP)
        self._max_resident = scope.gauge("max_resident")
        # bumped on every residency change (page-in / evict): engines key
        # their per-step AdapterContext cache on (slot ids, version), so a
        # context built over stale stacks can never serve a decode step
        self.version = 0

    # -- AdapterBank surface --------------------------------------------------
    @property
    def names(self) -> Tuple[str, ...]:
        """Every servable name (host tier), identity first — residency is
        an implementation detail of the fixed HBM budget."""
        return (peft_lib.BASE_ADAPTER,) + self.store.names

    @property
    def num_slots(self) -> int:
        return self.capacity + 1

    @property
    def bank_methods(self) -> Tuple[str, ...]:
        return self._methods

    @property
    def resident(self) -> Tuple[str, ...]:
        return tuple(self._resident)

    def is_resident(self, name: str) -> bool:
        """Is this adapter's factor set currently paged into HBM? The
        cluster router's affinity probe — warm here means admitting here
        skips the page-in entirely."""
        return name in self._resident

    def cfg_for(self, name: str) -> peft_lib.PEFTConfig:
        return self.store.cfg_for(name)

    def _unknown(self, name: str) -> KeyError:
        return KeyError(
            f"unknown adapter {name!r}; resident: "
            f"{sorted(self._resident)}; host store holds "
            f"{sorted(self.store.names)}")

    def validate(self, name: Optional[str]) -> None:
        """Raise KeyError (listing resident AND host-side names) unless
        ``name`` is servable. Does not touch residency."""
        if name is not None and name not in self.store:
            raise self._unknown(name)

    def slot(self, name: Optional[str]) -> int:
        """Universal slot of a RESIDENT adapter (None -> 0). Unlike the
        eager bank this can miss for a known name — admission goes through
        ``acquire``, which pages in."""
        if name is None:
            return 0
        rec = self._resident.get(name)
        if rec is None:
            if name in self.store:
                raise KeyError(
                    f"adapter {name!r} is in the store but not resident — "
                    "admission must go through acquire(), which pages it in")
            raise self._unknown(name)
        return rec[0]

    def context(self, slot_ids) -> peft_lib.AdapterContext:
        """Per-request context from UNIVERSAL slot ids: the host luts
        resolve them into per-method compact ids; the device graph then
        matches the padded bank exactly (one gather per method stack)."""
        ids = np.asarray(slot_ids, np.int32)
        slots = {m: jnp.asarray(self._lut[m][ids]) for m in self._methods}
        return peft_lib.AdapterContext(bank=self.tree, slots=slots,
                                       peft=self.cfg)

    # -- residency ------------------------------------------------------------
    def acquire(self, name: Optional[str]) -> Optional[int]:
        """Admission: pin ``name`` and return its universal slot, paging
        it in first on a miss. Returns None when every compact slot of the
        adapter's method is pinned by in-flight requests (admission stall
        — the caller keeps decoding resident slots and retries later).
        Balance every non-None acquire with ``release``."""
        if name is None:
            return 0
        if name not in self.store:
            raise self._unknown(name)
        rec = self._resident.get(name)
        if rec is not None:
            self._c["hits"].inc()
            self._lru.pop(name, None)
            self._lru[name] = None                   # move to MRU
            self._pins[name] = self._pins.get(name, 0) + 1
            return rec[0]

        method = self.store.method_of(name)
        if method not in self.caps:
            raise ValueError(
                f"adapter {name!r} uses method {method!r}, added to the "
                "store after this bank was built — re-attach to size a "
                "compact region for it")
        self._c["misses"].inc()
        if not self._free_compact[method]:
            victim = next((n for n in self._lru
                           if self._resident[n][1] == method
                           and not self._pins.get(n)), None)
            if victim is None:
                self._c["stalls"].inc()
                return None
            self._evict(victim)
        cslot = self._free_compact[method].pop()
        # every resident holds one universal + one compact slot, so a free
        # compact slot guarantees a free universal one
        uslot = self._free_universal.pop()

        t0 = time.perf_counter()
        self._page_in(name, method, cslot)
        self._page_in_ms.observe((time.perf_counter() - t0) * 1e3)
        self._lut[method][uslot] = cslot
        self._resident[name] = (uslot, method, cslot)
        self._lru[name] = None
        self._pins[name] = self._pins.get(name, 0) + 1
        self._max_resident.set_max(len(self._resident))
        return uslot

    def release(self, name: Optional[str]) -> None:
        """Request finished: unpin (the page stays resident until LRU
        eviction needs its compact slot)."""
        if name is None or name not in self._pins:
            return
        self._pins[name] -= 1
        if self._pins[name] <= 0:
            del self._pins[name]

    def _evict(self, name: str) -> None:
        self.version += 1
        uslot, method, cslot = self._resident.pop(name)
        self._lru.pop(name, None)
        self._lut[method][uslot] = 0                 # universal id -> identity
        self._free_universal.append(uslot)
        self._free_compact[method].append(cslot)
        self._c["evictions"].inc()
        # the stale page stays in the stack: nothing maps to its compact
        # slot until a new admission overwrites it

    # -- page materialization -------------------------------------------------
    def _pages_for(self, name: str,
                   method: str) -> Dict[str, Dict[str, np.ndarray]]:
        """Built (pre-processed) factor pages for one adapter, one per
        adapted path — from the host page cache, else ``bank_build`` over
        the store's raw params (pulled lazily from disk if backed)."""
        cached = self._page_cache.get(name)
        if cached is not None:
            self._c["build_cache_hits"].inc()
            return cached
        self._c["builds"].inc()
        mcfg = self.store.cfg_of_method(method)
        ops = methods_lib.get(method)
        raw = self.store.adapters_for(name)
        pages: Dict[str, Dict[str, np.ndarray]] = {}
        for path, spec in self._specs.items():
            if path not in raw:
                raise KeyError(f"adapter {name!r} has no params for {path}")
            shape = tuple(spec.batch) + (spec.d_in, spec.d_out)
            mspec = peft_lib.spec_for(mcfg, shape)
            built = ops.bank_build(mspec, [raw[path]])     # A=1 stack
            axis = len(mspec.batch)
            pages[path] = {k: np.asarray(jax.device_get(
                jnp.take(v, 0, axis=axis))) for k, v in built.items()}
        self._page_cache[name] = pages
        return pages

    def _page_in(self, name: str, method: str, cslot: int) -> None:
        self.version += 1
        pages = self._pages_for(name, method)
        for path, page in pages.items():
            idx = (slice(None),) * self._axis[path] + (cslot,)
            entry = self._stacks[path][method]
            for k in entry:
                entry[k] = entry[k].at[idx].set(
                    jnp.asarray(page[k], entry[k].dtype))
        jax.block_until_ready(
            [self._stacks[p][method][k] for p, pg in pages.items()
             for k in pg])

    def place(self, sharding) -> None:
        """Commit every stack to ``sharding`` (a replica's own device or
        its serve mesh). Page-ins update the stacks in place, so they keep
        the placement."""
        for entry in self._stacks.values():
            for factors in entry.values():
                for k, v in factors.items():
                    factors[k] = jax.device_put(v, sharding)

    # -- accounting -----------------------------------------------------------
    def resident_bytes(self) -> int:
        """HBM held by the compact stacks (identity slots included)."""
        return sum(int(arr.size * arr.dtype.itemsize)
                   for entry in self._stacks.values()
                   for factors in entry.values()
                   for arr in factors.values())

    def padded_bytes(self) -> int:
        """What the SAME universal capacity would cost in the eager padded
        representation: every method stack spanning all capacity+1 slots
        (identities at other methods' slots) instead of its c_m+1."""
        total = 0
        for entry in self._stacks.values():
            for m, factors in entry.items():
                per_slot = sum(int(a.size * a.dtype.itemsize)
                               for a in factors.values()) // (self.caps[m] + 1)
                total += per_slot * (self.capacity + 1)
        return total

    @property
    def counters(self) -> Dict[str, Any]:
        """Read-only value view of the bank's registry instruments, keyed
        by the pre-obs short names (tests and tools read these)."""
        return {k: c.value for k, c in self._c.items()}

    def stats(self) -> Dict[str, Any]:
        """Thin view over the bank's registry instruments — same keys the
        pre-obs dict exposed; page-in percentiles now come from the
        bounded histogram."""
        c = self.counters
        resident = self.resident_bytes()
        padded = self.padded_bytes()
        seen = c["hits"] + c["misses"]
        return {
            "store_adapters": len(self.store),
            "methods": dict(self.caps),
            "capacity": self.capacity,
            "resident": len(self._resident),
            "max_resident": self._max_resident.value,
            "hits": c["hits"],
            "misses": c["misses"],
            "hit_rate": c["hits"] / seen if seen else 0.0,
            "evictions": c["evictions"],
            "admission_stalls": c["stalls"],
            "builds": c["builds"],
            "build_cache_hits": c["build_cache_hits"],
            "page_in_ms_p50": self._page_in_ms.percentile(50),
            "page_in_ms_p95": self._page_in_ms.percentile(95),
            "resident_bank_bytes": resident,
            "padded_bank_bytes": padded,
            "compaction_ratio": padded / resident if resident else 0.0,
        }
