"""The benchmark's own clock on the serving engines' lifecycle hooks.

``PagedServeEngine`` calls a tracer at each request's submit, prompt
chunk, first token, decoded token and finish. ``Probe`` takes that place
(and watches each adapter bank's admissions for page-ins):
it stamps each hook with ``time.perf_counter()`` when the host passes it,
so every latency the benchmark reports is timed by the benchmark, from
the request's due time, and none is read from the program. With
``annotate`` on, the engines' named dispatches (``prefill_chunk``,
``decode``) become ``jax.profiler.TraceAnnotation`` spans on the
profiler's clock.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

Key = Tuple[str, int]


class Probe:
    def __init__(self, annotate: bool = False):
        self.jax_annotations = annotate
        self._tags: Dict[str, int] = {}
        self._keys: Dict[Key, int] = {}
        self._last: Optional[Key] = None
        self._moving: Optional[int] = None
        self.t_chunk0: Dict[int, float] = {}     # first prompt chunk sent
        self.t_first: Dict[int, float] = {}
        self.t_done: Dict[int, float] = {}
        self.token_times: Dict[int, List[float]] = {}
        self.prompt_len: Dict[int, int] = {}
        self._active: set = set()
        #: (time, rows decoding, keys they attend to) at each decode
        #: dispatch, recorded while annotating (a traced run)
        self.decode_log: List[Tuple[float, int, int]] = []
        #: (adapter, time) of each page-in into a watched bank
        self.page_ins: List[Tuple[str, float]] = []

    def watch_bank(self, bank) -> None:
        """Stamp each admission that pages an adapter into ``bank``."""
        acquire = bank.acquire

        def watched(name):
            missed = name is not None and not bank.is_resident(name)
            slot = acquire(name)
            if missed and slot is not None:
                self.page_ins.append((name, time.perf_counter()))
            return slot
        bank.acquire = watched

    # -- binding engine ids to the benchmark's request index -----------------
    def bind(self, idx: int, prompt_len: int) -> None:
        """The request just submitted is the benchmark's request ``idx``."""
        self._keys[self._last] = idx
        self.token_times[idx] = []
        self.prompt_len[idx] = prompt_len

    def _idx(self, tag: str, rid: int) -> Optional[int]:
        return self._keys.get((tag, rid))

    # -- the hooks the engines call -------------------------------------------
    def register_engine(self, kind: str = "engine") -> str:
        n = self._tags.get(kind, 0)
        self._tags[kind] = n + 1
        return f"{kind}{n}"

    def submit(self, tag: str, rid: int, adapter=None, prompt_len: int = 0,
               t_submit: Optional[float] = None) -> None:
        if self._moving is not None:          # re-submitted by a rebalance
            self._keys[(tag, rid)] = self._moving
            self._moving = None
        else:
            self._last = (tag, rid)

    def drop(self, tag: str, rid: int) -> None:
        self._moving = self._keys.pop((tag, rid), None)

    def stall(self, tag: str, rid: int, reason: str) -> None:
        pass

    def prefill_start(self, tag: str, rid: int) -> None:
        i = self._idx(tag, rid)
        if i is not None:
            self.t_chunk0.setdefault(i, time.perf_counter())

    def prefill_end(self, tag: str, rid: int) -> None:
        pass

    def first_token(self, tag: str, rid: int) -> None:
        i = self._idx(tag, rid)
        if i is not None:
            t = time.perf_counter()
            self.t_first[i] = t
            self.token_times[i].append(t)
            self._active.add(i)

    def token(self, tag: str, rid: int) -> None:
        i = self._idx(tag, rid)
        if i is not None:
            self.token_times[i].append(time.perf_counter())

    def finish(self, tag: str, rid: int) -> None:
        i = self._idx(tag, rid)
        if i is not None:
            self.t_done[i] = time.perf_counter()
            self._active.discard(i)

    def annotate(self, name: str):
        if not self.jax_annotations:
            return contextlib.nullcontext()
        if name == "decode":
            # each decoding row attends to its prompt and every token so
            # far, the one being fed included
            keys = sum(self.prompt_len[i] + len(self.token_times[i])
                       for i in self._active)
            self.decode_log.append((time.perf_counter(), len(self._active),
                                    keys))
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)
