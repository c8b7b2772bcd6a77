"""Serving engines: continuous batching with slot-based KV cache + the
static-batch reference engine. Both are drivers over ONE ``ModelRuntime``
(`repro.core.runtime`), which owns the jitted prefill/decode closures and
the optional per-request adapter bank.

``ServeEngine`` (the default) is a scheduler over ``max_batch`` persistent
decode slots:

  * requests are admitted into free slots as others finish (EOS or token
    budget) — no lockstep ``max(max_new_tokens)`` barrier;
  * each slot carries its own position counter; decode runs ONE jitted step
    over the full slot array with per-slot write positions and per-slot
    ``kv_len`` masks (the online-attention kv_len argument);
  * admission prefills a single request (batch 1, prompt padded to a
    power-of-two bucket to bound recompiles) and scatters the fresh state
    row into the slot (``train.steps.build_slot_prefill_step``);
  * when the runtime carries an ``AdapterBank``, each slot's id flows
    through an ``AdapterContext`` pytree: row i rotates its activations
    with its own orthogonal adapter x Q_i before every adapted matmul —
    O(b*d) per token, versus O(d^2) to re-merge a dense rotation per
    request. The bank is method-generic (any bankable ``core.methods``
    entry: GSOFT, OFT, BOFT, Householder) and may be HETEROGENEOUS —
    each named adapter declares its own method, so one deployment serves
    gsoft and boft and householder tenants side by side. Slot 0 of the
    bank is the universal identity (serves the base model).

``StaticServeEngine`` is the drain-queue -> pad -> prefill -> lockstep
decode reference (the paper's merged-weight serving story, §6.1): one
adapter merged into the weights offline (``ModelRuntime(adapters=...,
peft_cfg=...)``), zero per-token overhead. Use it when every request shares
one fine-tune; use ``ServeEngine`` over a banked runtime when requests
carry different adapters.

Both engines sample each row's first token at its OWN last valid prompt
index (ragged prompts — shorter rows no longer read a padded position) and
decode with per-row positions. Sharding-ready: build the runtime with a
mesh to shard params/caches like the dry-run does.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core.peft import PrefillRequest
from repro.core.runtime import ModelRuntime
from repro.models import registry
from repro.obs.metrics import REGISTRY
from .kv import KVPagePool, SlotPages, pages_for_budget


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    adapter: Optional[str] = None        # bank adapter name (None = base)
    output: Optional[List[int]] = None
    # timing (perf_counter seconds; filled by the engines)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


class EngineMetrics:
    """An engine's stats surface, backed by the process metrics plane.

    Writes go through the typed methods below (only engines call those);
    reads keep the ``eng.stats["requests"]`` dict-style surface every
    test, bench and driver already uses — same keys as the pre-obs dict,
    one source of truth in ``repro.obs.REGISTRY``. ``admission_log``
    stays a live bounded list: it is a diagnostics ring of
    ``(rid, decode_step)`` tuples, not a scalar instrument.
    """

    COUNTER_KEYS = ("requests", "tokens_generated", "decode_steps",
                    "prefills", "admission_stalls")

    def __init__(self, kind: str = "serve"):
        scope = REGISTRY.scope(kind)
        self._c = scope.counters(*self.COUNTER_KEYS)
        self._wall = scope.counter("wall_s")
        self.admission_log: List[Any] = []

    # -- writes (engine-internal) ---------------------------------------------
    def inc(self, key: str, n: int = 1) -> None:
        self._c[key].inc(n)

    def add_wall(self, dt: float) -> None:
        self._wall.inc(dt)

    def log_admission(self, rid: int) -> None:
        log = self.admission_log
        log.append((rid, self._c["decode_steps"].value))
        if len(log) > 4096:          # diagnostics ring, not a ledger
            del log[:-2048]

    # -- dict-style reads ------------------------------------------------------
    def __getitem__(self, key: str) -> Any:
        if key == "admission_log":
            return self.admission_log
        if key == "wall_s":
            return self._wall.value
        return self._c[key].value

    def __contains__(self, key: str) -> bool:
        return (key in self.COUNTER_KEYS
                or key in ("wall_s", "admission_log"))

    def as_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {k: c.value for k, c in self._c.items()}
        out["wall_s"] = self._wall.value
        out["admission_log"] = list(self.admission_log)
        return out


def _stream_prefix(cfg: ModelConfig) -> int:
    """Non-text positions prepended to the decode stream (vlm patches)."""
    return cfg.frontend_tokens if registry.get(cfg.family).has_patches else 0


def _check_token_family(cfg: ModelConfig) -> None:
    """Token engines need a prefill/decode surface; stateless families
    (``FamilyOps.stateless`` — whole-input forward, no KV) are served by
    ``serve.image.ImageServeEngine`` instead."""
    if registry.get(cfg.family).stateless:
        raise ValueError(
            f"family {cfg.family!r} is stateless (no prefill/decode "
            "surface) — serve it through serve.image.ImageServeEngine")


def _check_capacity(cfg: ModelConfig, prompt: List[int], max_new: int,
                    max_len: int) -> None:
    plen = len(prompt) + _stream_prefix(cfg)
    if plen + max_new > max_len:
        raise ValueError(f"prompt ({plen}) + max_new ({max_new}) "
                         f"exceeds max_len={max_len}")


def _family_feed(cfg: ModelConfig, toks: np.ndarray,
                 enc_len: int) -> Dict[str, Any]:
    """Prefill feed for a (B, S) token block, plus the per-family extra
    streams (encdec frames / vlm patches) — shared by both engines."""
    feed: Dict[str, Any] = {"tokens": jnp.asarray(toks)}
    b = toks.shape[0]
    t = registry.get(cfg.family)
    if t.has_encoder:
        feed["frames"] = jnp.zeros((b, enc_len, cfg.d_model), cfg.act_dtype)
    if t.has_patches:
        feed["patches"] = jnp.zeros(
            (b, cfg.frontend_tokens, cfg.frontend_dim), cfg.act_dtype)
    return feed


def latency_percentiles(requests: List[Request],
                        qs=(50, 95)) -> Dict[int, float]:
    """{q: seconds} request-latency percentiles over finished Requests."""
    lats = [r.latency_s for r in requests]
    if not lats:
        return {q: 0.0 for q in qs}
    return {q: float(np.percentile(lats, q)) for q in qs}


class ServeEngine:
    """Continuous-batching engine over ``max_batch`` persistent slots,
    driving one ``ModelRuntime``.

    ``tracer``: an optional ``repro.obs.TraceRecorder``; when attached the
    tick loop records each request's lifecycle spans (submit / stalls /
    prefill / tokens / finish). ``tracer=None`` (the default) skips every
    hook — tracing costs nothing when off and <5% when on (serve_bench
    asserts the bound).
    """

    _kind = "serve"          # metrics-scope prefix + tracer tag family

    def __init__(self, runtime: ModelRuntime, *, max_batch: int = 8,
                 max_len: int = 256, eos_id: int = 0, tracer=None):
        _check_token_family(runtime.cfg)
        self.rt = runtime
        self.cfg = runtime.cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.tracer = tracer
        self._ttag = (tracer.register_engine(self._kind)
                      if tracer is not None else "")
        self._annot = (tracer.annotate if tracer is not None
                       else lambda name: contextlib.nullcontext())
        self._enc_len = max(max_len // 4, 8)
        self._prefix = _stream_prefix(self.cfg)

        self._setup_compute()

        # per-slot bookkeeping (host side)
        self._pos = np.zeros(max_batch, np.int32)
        self._last = np.zeros(max_batch, np.int32)
        self._slot_ids = np.zeros(max_batch, np.int32)
        self._slot_req: List[Optional[Request]] = [None] * max_batch
        self._outs: List[List[int]] = [[] for _ in range(max_batch)]

        self._queue: "collections.deque[Request]" = collections.deque()
        self._next_id = 0
        self._results: Dict[int, List[int]] = {}
        # completed Requests (latency accounting). Grows until drained —
        # long-running streaming drivers should call drain_finished()
        # periodically instead of letting history accumulate.
        self.finished: List[Request] = []
        self.stats = EngineMetrics(self._kind)
        # decode-loop AdapterContext cache (satellite: the store-paged lane
        # used to rebuild the context — host LUT indexing + H2D per method —
        # on EVERY decode step; see _context())
        self._ctx_key: Any = None
        self._ctx_val = None

    def _setup_compute(self) -> None:
        """Jitted closures + device state (overridden by the paged engine)."""
        self._slot_prefill = self.rt.slot_prefill_fn(self.max_len,
                                                     self._enc_len)
        self._decode = self.rt.decode_fn()
        self._state = self.rt.decode_state(self.max_batch, self.max_len,
                                           enc_len=self._enc_len)

    # -- submission -----------------------------------------------------------
    def add_request(self, prompt: List[int], max_new_tokens: int = 16,
                    adapter: Optional[str] = None) -> int:
        # validate eagerly: raises on a name neither resident nor in the
        # host store, or on naming an adapter when the runtime has no bank
        self.rt.validate_adapter(adapter)
        _check_capacity(self.cfg, prompt, max_new_tokens, self.max_len)
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, list(prompt), max_new_tokens, adapter=adapter,
                      t_submit=time.perf_counter())
        self._queue.append(req)
        if self.tracer is not None:
            self.tracer.submit(self._ttag, rid, adapter=adapter,
                               prompt_len=len(prompt),
                               t_submit=req.t_submit)
        return rid

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def queue_depth(self) -> int:
        """Requests admitted but not yet slotted (router load signal)."""
        return len(self._queue)

    @property
    def load(self) -> int:
        """Queued + in-flight work — the cluster router's balance metric."""
        return self.queue_depth + self.num_active

    @property
    def idle(self) -> bool:
        return not self._queue and self.num_active == 0

    def add_wall(self, dt: float) -> None:
        """Account driver wall time (drivers call this instead of poking
        ``stats`` so the cluster can aggregate it the same way)."""
        self.stats.add_wall(dt)

    # -- cluster hooks (distrib.cluster) --------------------------------------
    def steal_queued(self) -> Optional[Request]:
        """Pop the YOUNGEST queued (never-admitted) request so the cluster
        can rebalance it onto a less-loaded replica; None when empty.
        Stealing from the tail keeps FIFO order for what stays."""
        if not self._queue:
            return None
        req = self._queue.pop()
        if self.tracer is not None:        # re-submits on the new engine
            self.tracer.drop(self._ttag, req.rid)
        return req

    def submit(self, req: Request) -> int:
        """Enqueue an existing Request under a FRESH local rid (rebalanced
        arrivals keep their submit timestamp/adapter; rids are per-engine,
        so a moved request must be re-keyed by the caller)."""
        self.rt.validate_adapter(req.adapter)
        _check_capacity(self.cfg, req.prompt, req.max_new_tokens,
                        self.max_len)
        req.rid = self._next_id
        self._next_id += 1
        self._queue.append(req)
        if self.tracer is not None:        # keeps the ORIGINAL submit time
            self.tracer.submit(self._ttag, req.rid, adapter=req.adapter,
                               prompt_len=len(req.prompt),
                               t_submit=req.t_submit)
        return req.rid

    # -- internals ------------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        """Power-of-two prompt pad length (bounds prefill recompiles);
        clamped so prefix + bucket always fits the slot cache."""
        b = 8
        while b < plen:
            b *= 2
        return min(b, self.max_len - self._prefix)

    def _feed(self, prompt: List[int]) -> Dict[str, Any]:
        bucket = self._bucket(len(prompt))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(prompt)] = prompt
        return _family_feed(self.cfg, toks, self._enc_len)

    def _finish(self, slot: int) -> None:
        req = self._slot_req[slot]
        req.output = self._outs[slot][:req.max_new_tokens]
        req.t_done = time.perf_counter()
        self._results[req.rid] = req.output
        self.finished.append(req)
        self.stats.inc("requests")
        self.stats.inc("tokens_generated", len(req.output))
        if self.tracer is not None:
            self.tracer.finish(self._ttag, req.rid)
        self._slot_req[slot] = None
        self._slot_ids[slot] = 0            # identity until re-admitted
        self.rt.release_adapter(req.adapter)   # unpin (store-backed banks)

    def _admit(self) -> None:
        """Fill free slots from the queue: single-request prefill, scatter
        the fresh state into the slot, sample the first token. On a
        store-backed runtime admission may page the adapter into HBM;
        when every page of its method is pinned by in-flight requests the
        acquire STALLS (FIFO head-of-line) — we stop admitting and keep
        decoding resident slots, which is what eventually unpins pages."""
        for slot in range(self.max_batch):
            if not self._queue:
                return
            if self._slot_req[slot] is not None:
                continue
            req = self._queue[0]
            aid = self.rt.acquire_adapter(req.adapter)
            if aid is None:                  # admission stall, not an error
                self.stats.inc("admission_stalls")
                if self.tracer is not None:
                    self.tracer.stall(self._ttag, req.rid, "adapter")
                return
            self._queue.popleft()
            last_idx = self._prefix + len(req.prompt) - 1
            feed = PrefillRequest(batch=self._feed(req.prompt),
                                  last_idx=jnp.asarray(last_idx, jnp.int32),
                                  ctx=self.rt.context([aid]))
            if self.tracer is not None:
                self.tracer.prefill_start(self._ttag, req.rid)
            with self._annot("prefill"):
                first, self._state = self._slot_prefill(
                    self.rt.params, feed, self._state,
                    jnp.asarray(slot, jnp.int32))
            first = int(first)
            req.t_first = time.perf_counter()
            if self.tracer is not None:
                self.tracer.prefill_end(self._ttag, req.rid)
                self.tracer.first_token(self._ttag, req.rid)
            self.stats.inc("prefills")
            self.stats.log_admission(req.rid)
            self._slot_req[slot] = req
            self._outs[slot] = [first]
            self._pos[slot] = self._prefix + len(req.prompt)
            self._last[slot] = first
            self._slot_ids[slot] = aid
            if first == self.eos_id or req.max_new_tokens <= 1:
                self._finish(slot)
        # every slot is occupied and work is still queued: head-of-line
        # wait on a decode slot, not on a resource
        if self._queue and self.tracer is not None:
            self.tracer.stall(self._ttag, self._queue[0].rid, "queue")

    def _context(self):
        """AdapterContext for the current slot ids, cached across decode
        steps. Rebuilding it is host work (numpy LUT indexing + one H2D per
        method) that used to run EVERY step — the store-paged serve
        regression. The cache key is (slot ids, bank version): page-in /
        eviction bumps ``bank.version`` so a stale gather can never serve."""
        key = (tuple(int(i) for i in self._slot_ids),
               getattr(self.rt.bank, "version", 0))
        if key != self._ctx_key:
            self._ctx_val = self.rt.context(self._slot_ids)
            self._ctx_key = key
        return self._ctx_val

    def _row_active(self, slot: int) -> bool:
        """Is this slot decoding? (The paged engine parks slots that are
        still mid-chunked-prefill.)"""
        return self._slot_req[slot] is not None

    def _decode_launch(self):
        """Dispatch one jitted decode step over the full slot array and
        return the PENDING next-token array without syncing it. JAX
        dispatch is async: the device crunches while the host moves on —
        which is exactly what lets an ``EngineCluster`` launch every
        replica's tick before blocking on any of them."""
        tokens = jnp.asarray(self._last[:, None])
        pos = jnp.asarray(self._pos)
        ctx = self._context()
        with self._annot("decode"):
            nt, _, self._state = self._decode(self.rt.params, ctx, tokens,
                                              self._state, pos)
        self.stats.inc("decode_steps")
        return nt

    def _decode_commit(self, nt) -> None:
        """Sync the launched step's tokens and advance slot bookkeeping."""
        vals = np.asarray(nt[:, 0])
        for slot in range(self.max_batch):
            if not self._row_active(slot):
                continue
            req = self._slot_req[slot]
            tok = int(vals[slot])
            self._outs[slot].append(tok)
            self._pos[slot] += 1
            self._last[slot] = tok
            if self.tracer is not None:
                self.tracer.token(self._ttag, req.rid)
            if tok == self.eos_id or len(self._outs[slot]) >= req.max_new_tokens:
                self._finish(slot)

    def _decode_tick(self) -> None:
        """One jitted decode step over the full slot array."""
        self._decode_commit(self._decode_launch())

    def step_launch(self):
        """First half of ``step``: admit into free slots, dispatch the
        decode step, return the pending token array (None when no slot is
        decoding). Pass the result to ``step_commit`` — splitting the tick
        lets a multi-replica driver overlap every replica's device work."""
        self._admit()
        if self.num_active:
            return self._decode_launch()
        return None

    def step_commit(self, pending) -> bool:
        """Second half of ``step``: sync + bookkeep a launched tick.
        Returns True if any work remains queued or in flight."""
        if pending is not None:
            self._decode_commit(pending)
        return not self.idle

    def step(self) -> bool:
        """One scheduler tick: admit into free slots, then one decode step
        over all active slots. Returns True if any work remains queued or
        in flight (the streaming driver loop condition)."""
        return self.step_commit(self.step_launch())

    def drain_finished(self) -> List[Request]:
        """Hand over (and forget) everything completed so far — the
        bounded-memory accessor for long-running streaming loops (also
        releases the corresponding pending run() results)."""
        out, self.finished = self.finished, []
        for r in out:
            self._results.pop(r.rid, None)
        return out

    def adapter_stats(self) -> Optional[Dict[str, Any]]:
        """Residency counters of a store-backed bank — hit rate, page-in
        latency, evictions, resident/padded bytes (None on eager banks)."""
        stats = getattr(self.rt.bank, "stats", None)
        return stats() if callable(stats) else None

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue to completion; returns {rid: tokens}."""
        t0 = time.perf_counter()
        while self.step():
            pass
        self.stats.add_wall(time.perf_counter() - t0)
        res, self._results = self._results, {}
        return res


class StaticServeEngine:
    """Static-batch reference: drain queue -> pad -> prefill -> lockstep
    decode. Adapters (one per deployment) are merged into the runtime's
    weights offline — the paper's zero-overhead serving mode."""

    _kind = "static"

    def __init__(self, runtime: ModelRuntime, *, max_batch: int = 8,
                 max_len: int = 256, eos_id: int = 0, tracer=None):
        _check_token_family(runtime.cfg)
        if runtime.banked:
            raise ValueError(
                "static serving merges ONE adapter offline "
                "(ModelRuntime(adapters=..., peft_cfg=...)); per-request "
                "banks need the continuous ServeEngine")
        self.rt = runtime
        self.cfg = runtime.cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.tracer = tracer
        self._ttag = (tracer.register_engine(self._kind)
                      if tracer is not None else "")
        self._annot = (tracer.annotate if tracer is not None
                       else lambda name: contextlib.nullcontext())
        self._queue: List[Request] = []
        self._next_id = 0
        self.finished: List[Request] = []    # completed Requests (latency)
        self._prefill = runtime.prefill_fn()
        self._decode = runtime.decode_fn()
        self.stats = EngineMetrics(self._kind)

    def add_request(self, prompt: List[int], max_new_tokens: int = 16) -> int:
        _check_capacity(self.cfg, prompt, max_new_tokens, self.max_len)
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, list(prompt), max_new_tokens,
                      t_submit=time.perf_counter())
        self._queue.append(req)
        if self.tracer is not None:
            self.tracer.submit(self._ttag, rid, prompt_len=len(prompt),
                               t_submit=req.t_submit)
        return rid

    def drain_finished(self) -> List[Request]:
        """Hand over (and forget) the completed-Request history."""
        out, self.finished = self.finished, []
        return out

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def add_wall(self, dt: float) -> None:
        self.stats.add_wall(dt)

    # -- internals ------------------------------------------------------------
    def _run_batch(self, batch: List[Request]) -> None:
        b = len(batch)
        prefix = _stream_prefix(self.cfg)
        plen = max(len(r.prompt) for r in batch)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(batch):
            toks[i, :len(r.prompt)] = r.prompt          # right-padded
        enc_len = max(plen // 4, 8)
        state = self.rt.decode_state(b, self.max_len, enc_len=enc_len)
        feed = _family_feed(self.cfg, toks, enc_len)
        # ragged fix: each row samples at its OWN last prompt position and
        # decodes from its own position counter — padded rows no longer read
        # (or attend over) the pad tail
        last_idx = np.asarray([prefix + len(r.prompt) - 1 for r in batch],
                              np.int32)
        if self.tracer is not None:
            for r in batch:
                self.tracer.prefill_start(self._ttag, r.rid)
        req = PrefillRequest(batch=feed, last_idx=jnp.asarray(last_idx))
        with self._annot("prefill"):
            logits, state = self._prefill(self.rt.params, req, state)
        last = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        self.stats.inc("prefills")
        for r in batch:
            r.t_first = time.perf_counter()
            if self.tracer is not None:
                self.tracer.prefill_end(self._ttag, r.rid)
                self.tracer.first_token(self._ttag, r.rid)

        max_new = max(r.max_new_tokens for r in batch)
        outs = [[int(last[i, 0])] for i in range(b)]
        done = np.asarray([outs[i][0] == self.eos_id or
                           r.max_new_tokens <= 1
                           for i, r in enumerate(batch)])
        pos0 = np.asarray([prefix + len(r.prompt) for r in batch], np.int32)
        for t in range(max_new - 1):
            if done.all():
                break
            with self._annot("decode"):
                nt, logits, state = self._decode(self.rt.params, None, last,
                                                 state, jnp.asarray(pos0 + t))
            self.stats.inc("decode_steps")
            last = nt
            vals = np.asarray(nt[:, 0])
            for i in range(b):
                if not done[i]:
                    outs[i].append(int(vals[i]))
                    if self.tracer is not None:
                        self.tracer.token(self._ttag, batch[i].rid)
                    done[i] |= vals[i] == self.eos_id or \
                        len(outs[i]) >= batch[i].max_new_tokens
            if done.all():
                break
        for i, r in enumerate(batch):
            r.output = outs[i][:r.max_new_tokens]
            r.t_done = time.perf_counter()
            self.stats.inc("tokens_generated", len(r.output))
            if self.tracer is not None:
                self.tracer.finish(self._ttag, r.rid)

    def run(self) -> Dict[int, List[int]]:
        t0 = time.perf_counter()
        results: Dict[int, List[int]] = {}
        while self._queue:
            batch = self._queue[:self.max_batch]
            self._queue = self._queue[self.max_batch:]
            self._run_batch(batch)
            for r in batch:
                results[r.rid] = r.output
                self.finished.append(r)
                self.stats.inc("requests")
        self.stats.add_wall(time.perf_counter() - t0)
        return results


@dataclasses.dataclass
class _PrefillPlan:
    """One admitted request's remaining chunked-prefill work."""
    slot: int
    req: Request
    sp: SlotPages
    next_start: int          # absolute position of the next chunk's 1st token


class PagedServeEngine(ServeEngine):
    """Continuous batching over a PAGED KV cache with chunked prefill.

    Three changes against the contiguous parent (ISSUE 7):

      * HBM: slots own fixed-size pages from one static pool (sized by
        ``hbm_kv_budget`` or ``num_pages``) through per-slot int32 page
        tables — a short request pays ceil(len / page_size) pages, not
        ``max_len`` rows; when the pool is exhausted admission STALLS
        (``kv_stalls`` counter) instead of over-subscribing.
      * Admission: prompts prefill in ``prefill_chunk``-token chunks, ONE
        chunk per scheduler tick, interleaved with decode — a long prompt
        delays decoding slots by one chunk per tick instead of
        head-of-line-blocking them for its whole prefill.
      * Shared prefixes: full prompt pages are content-hashed (seeded by
        the adapter name) and refcount-shared across requests — N tenants
        with one system prompt pin ONE set of pages, and their prefill
        skips the cached tokens entirely (``prefix_hits``). Divergent
        suffixes are private by construction (see serve/kv.py).

    Greedy tokens are identical to ``ServeEngine`` (tests pin this); only
    residency and scheduling change. Decoder-family runtimes only.
    """

    _kind = "paged"

    def __init__(self, runtime: ModelRuntime, *, max_batch: int = 8,
                 max_len: int = 256, eos_id: int = 0, page_size: int = 8,
                 prefill_chunk: int = 16, num_pages: Optional[int] = None,
                 hbm_kv_budget: Optional[int] = None, tracer=None):
        if runtime._ops.init_paged_state is None:
            raise ValueError(
                f"family {runtime.cfg.family!r} has no paged KV serve path "
                "— use the contiguous ServeEngine")
        if page_size < 1 or prefill_chunk < 1:
            raise ValueError("page_size and prefill_chunk must be >= 1")
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.max_pages = -(-max_len // page_size)
        self._parked = self.max_pages * page_size   # sentinel write position
        if num_pages is None:
            if hbm_kv_budget is not None:
                num_pages = pages_for_budget(runtime.cfg, page_size,
                                             hbm_kv_budget)
            else:                       # stall-free default: worst case + 1
                num_pages = max_batch * self.max_pages + 1
        self.num_pages = num_pages
        super().__init__(runtime, max_batch=max_batch, max_len=max_len,
                         eos_id=eos_id, tracer=tracer)
        self._pos[:] = self._parked
        self._decoding = np.zeros(max_batch, bool)
        self._slot_pages: List[Optional[SlotPages]] = [None] * max_batch
        self._prefill_q: "collections.deque[_PrefillPlan]" = \
            collections.deque()
        self._zero_row = jnp.zeros(self.max_pages + 1, jnp.int32)

    def _setup_compute(self) -> None:
        self._decode = self.rt.paged_decode_fn()
        self._chunk_prefill = self.rt.chunk_prefill_fn()
        self.pool = KVPagePool(self.num_pages, self.page_size)
        self._state = self.rt.paged_state(self.max_batch, self.num_pages,
                                          self.page_size, self.max_pages)

    # -- scheduling -----------------------------------------------------------
    def _row_active(self, slot: int) -> bool:
        return bool(self._decoding[slot])

    def _admit(self) -> None:
        """Claim a slot + adapter + KV pages per queued request; the prompt
        itself is fed later, one chunk per tick (``_feed_one_chunk``).
        Either resource exhausted -> stall (stop admitting, keep decoding:
        finishing requests is what frees pages and unpins adapters)."""
        for slot in range(self.max_batch):
            if not self._queue:
                return
            if self._slot_req[slot] is not None:
                continue
            req = self._queue[0]
            aid = self.rt.acquire_adapter(req.adapter)
            if aid is None:
                self.stats.inc("admission_stalls")
                if self.tracer is not None:
                    self.tracer.stall(self._ttag, req.rid, "adapter")
                return
            sp = self.pool.admit(req.adapter, req.prompt, req.max_new_tokens)
            if sp is None:                        # KV stall, not an error
                self.rt.release_adapter(req.adapter)
                self.stats.inc("admission_stalls")
                if self.tracer is not None:
                    self.tracer.stall(self._ttag, req.rid, "kv")
                return
            self._queue.popleft()
            row = self.pool.table_row(sp, self.max_pages + 1)
            self._state["table"] = \
                self._state["table"].at[slot].set(jnp.asarray(row))
            self._slot_req[slot] = req
            self._slot_ids[slot] = aid
            self._slot_pages[slot] = sp
            self._outs[slot] = []
            self._decoding[slot] = False
            self._pos[slot] = self._parked        # writes park in garbage
            self._prefill_q.append(_PrefillPlan(slot, req, sp,
                                                next_start=sp.n_cached))
        if self._queue and self.tracer is not None:     # all slots occupied
            self.tracer.stall(self._ttag, self._queue[0].rid, "queue")

    def _feed_one_chunk(self) -> None:
        """Advance the HEAD prefill plan by one fixed-width chunk. The last
        chunk yields the request's first token and flips the slot to
        decoding; cached-prefix tokens were never fed at all."""
        if not self._prefill_q:
            return
        plan = self._prefill_q[0]
        req, slot = plan.req, plan.slot
        plen = len(req.prompt)
        start = plan.next_start
        end = min(start + self.prefill_chunk, plen)
        toks = np.zeros((1, self.prefill_chunk), np.int32)
        toks[0, :end - start] = req.prompt[start:end]
        final = end == plen
        last_local = (plen - 1) - start if final else end - start - 1
        feed = PrefillRequest(
            batch={"tokens": jnp.asarray(toks)},
            last_idx=jnp.asarray(last_local, jnp.int32),
            ctx=self.rt.context([self._slot_ids[slot]]))
        if self.tracer is not None:                # span per prompt chunk
            self.tracer.prefill_start(self._ttag, req.rid)
        with self._annot("prefill_chunk"):
            first, _, self._state = self._chunk_prefill(
                self.rt.params, feed, self._state,
                jnp.asarray(slot, jnp.int32), jnp.asarray(start, jnp.int32))
        if self.tracer is not None:
            self.tracer.prefill_end(self._ttag, req.rid)
        plan.next_start = end
        if not final:
            return
        self._prefill_q.popleft()
        self.pool.register(plan.sp)               # publish full prompt pages
        first = int(first)
        req.t_first = time.perf_counter()
        if self.tracer is not None:
            self.tracer.first_token(self._ttag, req.rid)
        self.stats.inc("prefills")
        self.stats.log_admission(req.rid)
        self._outs[slot] = [first]
        self._pos[slot] = plen
        self._last[slot] = first
        self._decoding[slot] = True
        if first == self.eos_id or req.max_new_tokens <= 1:
            self._finish(slot)

    def _finish(self, slot: int) -> None:
        sp = self._slot_pages[slot]
        super()._finish(slot)
        self._slot_pages[slot] = None
        self._decoding[slot] = False
        self._pos[slot] = self._parked
        self._last[slot] = 0
        self._state["table"] = \
            self._state["table"].at[slot].set(self._zero_row)
        self.pool.finish(sp)

    def step_launch(self):
        """One tick's dispatch half: admit, feed ONE prompt chunk, launch
        one decode step over the decoding slots. Decode latency is bounded
        by one chunk of prefill per tick — never a whole prompt. (``step``
        composes this with the inherited ``step_commit``.)"""
        self._admit()
        self._feed_one_chunk()
        if self._decoding.any():
            return self._decode_launch()
        return None

    def kv_stats(self) -> Dict[str, int]:
        """Page-pool residency counters (allocs, prefix hits, KV stalls,
        cache evictions, pages in use)."""
        return self.pool.stats()
