"""The one traffic generator. A mix is a data file (``traffic/<mix>.json``);
this module turns it and a seed into the requests or batches a run sends.

Every seed gets the same work in another order: each length and each
session's tenant is a stratified quantile of the mix's distribution (the
``(i + 0.5) / n`` quantiles of ``n`` draws), and the seed only permutes
them and draws the token ids. The arrival times are the same for every
seed (the gaps' order is drawn once, from a fixed stream). So two seeds
differ in which request is long, which tenant comes when and what the
tokens are, never in how much there is to do or when it comes, and a
run's spread is the system's and not the mix's.

Serving mixes are sessions. A session belongs to a tenant (Zipf over the
tenants) and shares a prefix: the tenant's system prompt
(``"scope": "tenant"``) or a document of its own (``"scope":
"session"``). Each of its ``turns`` asks appends a fresh user turn to the
prefix. Session starts are a Poisson process (exponential gaps) at
``rate_per_s / turns``; turn ``j`` is due ``j * turn_gap_s`` later, and a
turn that would fall past the window wraps round to its start, so every
window holds ``round(rate_per_s * seconds)`` asks.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    idx: int
    due: float                 # seconds after the window opens
    tenant: Optional[str]
    prompt: np.ndarray         # int32 token ids: prefix + user turn
    max_new: int
    prefix: int                # leading tokens shared with other requests
    session: int
    prefix_key: str = ""       # requests with one key share the prefix


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as ints
    clipped to ``[min, max]``: fixed, uniform, loguniform or lognormal
    (by ``median`` and ``sigma`` of the log)."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        v = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"] + 1)
    elif kind == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        v = np.exp(lo + u * (hi - lo))
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = spec.get("min", -math.inf), spec.get("max", math.inf)
    return np.clip(np.floor(v), lo, hi).astype(np.int64)


def zipf_tenants(n: int, tenants: int, alpha: float) -> np.ndarray:
    """Tenant rank of each of ``n`` sessions: the ``(i + 0.5) / n``
    quantiles of the popularity ``1 / (rank + 1) ** alpha``, so the tail
    of rarely seen tenants gets its share even in a short window."""
    p = 1.0 / np.arange(1, tenants + 1) ** alpha
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u, side="right"), tenants - 1)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose; seeds past 32 bits are fine."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  (int(seed) >> 32) & 0xFFFFFFFF, stream])


def tenant_names(mix: Dict[str, Any]) -> List[str]:
    return [f"tenant{i:03d}" for i in range(int(mix["tenants"]))]


def max_len(mix: Dict[str, Any], page: int) -> int:
    """Longest prompt plus answer the mix can send, in whole pages."""
    p = mix["prefix"]["tokens"]
    longest = (int(p.get("max", p.get("value", 0))) + int(mix["prompt"]["max"])
               + int(mix["output"]["max"]))
    return -(-longest // page) * page


def serve_requests(mix: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> List[Request]:
    """The asks due in a window of ``seconds``, sorted by due time."""
    turns = int(mix["prefix"].get("turns", 1))
    gap = float(mix["prefix"].get("turn_gap_s", 0.0))
    n_ask = max(1, round(mix["rate_per_s"] * seconds))
    n_sess = max(1, -(-n_ask // turns))
    order = rng_for(seed, 0)
    tokens = rng_for(seed, 1)
    names = tenant_names(mix)

    # session starts: exponential gaps (Poisson process), total = window
    mean = seconds / n_sess
    gaps = -mean * np.log1p(-(np.arange(n_sess) + 0.5) / n_sess)
    gaps = rng_for(0, 4).permutation(gaps) * (seconds / gaps.sum())
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    owners = order.permutation(
        zipf_tenants(n_sess, len(names), mix["zipf_alpha"]))

    scope = mix["prefix"]["scope"]
    if scope == "tenant":
        plen = quantiles(mix["prefix"]["tokens"], len(names))
        prefixes = [tokens.integers(0, vocab, int(n), dtype=np.int32)
                    for n in plen]
        sess_prefix = [prefixes[o] for o in owners]
    elif scope == "session":
        plen = order.permutation(quantiles(mix["prefix"]["tokens"], n_sess))
        sess_prefix = [tokens.integers(0, vocab, int(n), dtype=np.int32)
                       for n in plen]
    else:
        raise ValueError(f"unknown prefix scope {scope!r}")

    n_all = n_sess * turns
    user = order.permutation(quantiles(mix["prompt"], n_all))
    out = order.permutation(quantiles(mix["output"], n_all))
    reqs: List[Request] = []
    for s in range(n_sess):
        for j in range(turns):
            k = s * turns + j
            if k >= n_ask:
                break
            due = (starts[s] + j * gap) % seconds
            turn = tokens.integers(0, vocab, int(user[k]), dtype=np.int32)
            reqs.append(Request(
                idx=0, due=float(due), tenant=names[owners[s]],
                prompt=np.concatenate([sess_prefix[s], turn]),
                max_new=int(out[k]), prefix=len(sess_prefix[s]), session=s,
                prefix_key=(f"t{owners[s]}" if scope == "tenant"
                            else f"s{s}")))
    reqs.sort(key=lambda r: (r.due, r.session))
    for i, r in enumerate(reqs):
        r.idx = i
    return reqs


def warmup_requests(mix: Dict[str, Any], seed: int, vocab: int,
                    chunk: int) -> List[Request]:
    """Two asks of one tenant sharing a two-chunk prefix, with tokens of
    their own stream: together they run every program and host path the
    window runs (chunked prefill, a prefix hit, decode, finish)."""
    rng = rng_for(seed, 2)
    name = tenant_names(mix)[0]
    prefix = rng.integers(0, vocab, 2 * chunk, dtype=np.int32)
    return [Request(idx=-1 - i, due=0.0, tenant=name,
                    prompt=np.concatenate([prefix, rng.integers(
                        0, vocab, 8, dtype=np.int32)]),
                    max_new=4, prefix=len(prefix), session=-1)
            for i in range(2)]


def train_rows(mix: Dict[str, Any], seed: int, rows: int, vocab: int,
               stream: int) -> Dict[str, np.ndarray]:
    """``rows`` sequences of ``seq_len`` tokens drawn over the whole
    vocabulary; labels are the tokens shifted by one and every position
    counts. ``stream`` numbers the batch, so no two batches of a run
    share a row."""
    seq = int(mix["seq_len"])
    flat = rng_for(seed, 100 + stream).integers(
        0, vocab, (rows, seq + 1), dtype=np.int32)
    return {"tokens": flat[:, :-1], "labels": flat[:, 1:],
            "mask": np.ones((rows, seq), np.float32)}
