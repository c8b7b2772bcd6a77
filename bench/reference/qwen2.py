"""Plain float32 qwen2 (arXiv:2407.10671) with GSOFT adapters.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache, no
batching. It follows the published block:

    x = rms(h) * (1 + g_attn);  q, k, v = x Wq + bq, x Wk + bk, x Wv + bv
    rope(q), rope(k) (rotate-half, base theta); causal GQA softmax(q k^T /
    sqrt(hd)) v;  h += attn Wo
    x = rms(h) * (1 + g_mlp);  h += (silu(x Wg) * (x Wi)) Wdown
    logits = (rms(h) * (1 + g_final)) Whead

Weights are stored as the benchmark makes them (``harness/weights.py``):
``(d_in, d_out)`` matrices stacked over layers, norm gains as offsets
from 1. A GSOFT adapter rotates a weight's input side, W' = Q W with
Q = P^T L P R (paper eq. 1 with the GSOFT layout): L and R block-diagonal
with orthogonal b x b blocks, each the Cayley map (I + K)(I - K)^-1 of
K = A - A^T, and P the shuffle that sends row i to (i mod r) b + i div r
(r = d / b). The adapted weight is built here, weight side, from those
factors. A quantized weight path (``quant``: int8, int4 or fp8) scales
each output column symmetrically, ``scale = max|w| / qmax`` (qmax 127, 7,
or 448 for e4m3), rounds ``w / scale`` to the code (nearest integer
clipped to ``[-qmax, qmax]``, or the nearest e4m3 value) and computes
with ``code * scale``; with the suffix ``-wa`` (``fp8-wa``) the
activations entering each linear layer and the head are rounded the same
way, one scale per row: the model computed in that precision.

Everything runs in blocks (query blocks of attention, one projection at a
time, vocabulary blocks of the head) so that it fits beside the bf16
weights on one 16 GB chip at the published widths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGH = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
VOCAB_BLOCKS = 9


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    block: int

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Arch":
        heads = int(c["num_attention_heads"])
        return cls(d=int(c["hidden_size"]), heads=heads,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c.get("head_dim",
                                      int(c["hidden_size"]) // heads)),
                   d_ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   layers=int(c["num_hidden_layers"]),
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]),
                   block=int(c["deployment"]["adapter"]["block_size"]))


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def cayley(a):
    """(I + K)(I - K)^-1 of K = a - a^T, over the last two axes."""
    k = a - jnp.swapaxes(a, -1, -2)
    eye = jnp.eye(k.shape[-1], dtype=jnp.float32)
    # X (I - K) = I + K  <=>  (I - K)^T X^T = (I + K)^T = I - K
    xt = jnp.linalg.solve(jnp.swapaxes(eye - k, -1, -2),
                          jnp.swapaxes(eye + k, -1, -2))
    return jnp.swapaxes(xt, -1, -2)


def shuffle(d: int, block: int) -> np.ndarray:
    """Row map of P: row i of P W is row (i mod r) b + i div r of W."""
    r = d // block
    i = np.arange(d)
    return (i % r) * block + i // r


def gs_rotate_rows(w, a_l, a_r, block: int):
    """Q W for Q = P^T L P R, from the generators of L and R (r, b, b)."""
    d, n = w.shape
    r = d // block
    sig = shuffle(d, block)
    inv = np.argsort(sig)
    L, R = cayley(a_l), cayley(a_r)
    y = jnp.einsum("gij,gjn->gin", R, w.reshape(r, block, n),
                   precision=HIGH).reshape(d, n)
    y = y[sig]
    y = jnp.einsum("gij,gjn->gin", L, y.reshape(r, block, n),
                   precision=HIGH).reshape(d, n)
    return y[inv]


QMAX = {"int8": 127.0, "int4": 7.0, "fp8": 448.0}
#: suffix of a quantization that also rounds each matmul's activations
ACTS = "-wa"


def _rounded(x, quant: str, axis: int):
    """``x`` (float32) rounded to ``quant``'s code with one symmetric
    scale per slice along ``axis``, brought back to float32."""
    qmax = QMAX[quant]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / qmax
    if quant == "fp8":
        code = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        code = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    return code * scale


def dequant(w, quant: Optional[str]):
    """The weight as computed: float32, or its symmetric per-output-column
    quantization (``quant`` one of QMAX, with or without ACTS) brought
    back to float32."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    return _rounded(w, quant.removesuffix(ACTS), axis=0)


def act(x, quant: Optional[str]):
    """A matmul's activations as computed: as they are, or, for a
    quantization with the ACTS suffix (computed in that precision, not
    only stored in it), rounded with one scale per row. Serving controls
    only: the rounding has no gradient."""
    if quant is None or not quant.endswith(ACTS):
        return x
    return _rounded(x, quant.removesuffix(ACTS), axis=-1)


def rms(x, gain, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain.astype(jnp.float32))


def rope(x, theta: float):
    """x (S, heads, hd) at positions 0..S-1, rotate-half convention."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# jitted blocks (one compile per shape; each holds one weight at a time)
# ---------------------------------------------------------------------------

COLUMN_BLOCK = 4096


def column_blocks(n: int) -> int:
    """Fewest equal column blocks of at most COLUMN_BLOCK columns."""
    nb = -(-n // COLUMN_BLOCK)
    while n % nb:
        nb += 1
    return nb


@functools.partial(jax.jit, static_argnames=("block", "quant"))
def project(x, w, a_l, a_r, *, block: int, quant: Optional[str]):
    """x @ (Q deq(w)), the adapted weight built a block of columns at a
    time; no adapter when ``a_l`` is None."""
    d, n = w.shape
    nb = column_blocks(n)
    cb = n // nb

    def one(i):
        wf = dequant(jax.lax.dynamic_slice_in_dim(w, i * cb, cb, axis=1),
                     quant)
        if a_l is not None:
            wf = gs_rotate_rows(wf, a_l, a_r, block)
        return jnp.dot(xq, wf, precision=HIGH)

    xq = act(x, quant)
    y = jax.lax.map(one, jnp.arange(nb))           # (nb, S, cb)
    return jnp.swapaxes(y, 0, 1).reshape(x.shape[0], n)


@functools.partial(jax.jit, static_argnames=("arch",))
def attention(q, k, v, arch: Arch):
    """Causal GQA over the whole (padded) sequence, query block by block.
    q (S, H*hd), k/v (S, K*hd) -> (S, H*hd)."""
    s = q.shape[0]
    H, K, hd = arch.heads, arch.kv_heads, arch.head_dim
    g = H // K
    q = rope(q.reshape(s, H, hd), arch.theta) / np.sqrt(hd)
    k = rope(k.reshape(s, K, hd), arch.theta)
    v = v.reshape(s, K, hd)
    blk = min(QUERY_BLOCK, s)
    qb = q.reshape(s // blk, blk, K, g, hd)
    kpos = np.arange(s)

    def one(args):
        qblk, start = args
        sc = jnp.einsum("qkgd,ckd->kgqc", qblk, k, precision=HIGH)
        qpos = start + np.arange(blk)
        mask = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HIGH)

    starts = jnp.arange(0, s, blk)
    out = jax.lax.map(one, (qb, starts))
    return out.reshape(s, H * hd)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, gain, *, eps: float):
    return rms(x, gain, eps)


@jax.jit
def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


@functools.partial(jax.jit, static_argnames=("quant",))
def head_block(h, w, *, quant: Optional[str]):
    """Logits of one vocabulary block: h (n, d) @ deq(w) (d, vb)."""
    return jnp.dot(act(h, quant), dequant(w, quant), precision=HIGH)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _adapter(ad: Optional[Dict[str, Any]], path: str, layer: int):
    if ad is None or path not in ad:
        return None, None
    return (jnp.asarray(ad[path]["L"][layer], jnp.float32),
            jnp.asarray(ad[path]["R"][layer], jnp.float32))


def hidden(arch: Arch, params, adapter, tokens: np.ndarray,
           quant: Optional[str]):
    """Final-normed hidden states (S, d) f32 of one sequence; ``adapter``
    is the tenant's {path: {"L", "R"}} generators (None: base model).
    ``tokens`` is padded to a multiple of the query block; padding only
    follows the real tokens, so causality keeps it out of them."""
    lay = params["layers"]
    h = jnp.take(params["embed"]["table"], jnp.asarray(tokens),
                 axis=0).astype(jnp.float32)
    b = arch.block
    for l in range(arch.layers):
        x = _norm(h, lay["attn_norm"][l], eps=arch.eps)
        qkv = []
        for name in ("wq", "wk", "wv"):
            a_l, a_r = _adapter(adapter, f"layers/attn/{name}", l)
            y = project(x, lay["attn"][name][l], a_l, a_r, block=b,
                        quant=quant)
            bias = lay["attn"].get("b" + name[1])
            if bias is not None:
                y = y + bias[l].astype(jnp.float32)
            qkv.append(y)
        a = attention(*qkv, arch=arch)
        a_l, a_r = _adapter(adapter, "layers/attn/wo", l)
        h = h + project(a, lay["attn"]["wo"][l], a_l, a_r, block=b,
                        quant=quant)
        x = _norm(h, lay["mlp_norm"][l], eps=arch.eps)
        ys = {}
        for name in ("wg", "wi"):
            a_l, a_r = _adapter(adapter, f"layers/mlp/{name}", l)
            ys[name] = project(x, lay["mlp"][name][l], a_l, a_r, block=b,
                               quant=quant)
        m = _swiglu(ys["wg"], ys["wi"])
        a_l, a_r = _adapter(adapter, "layers/mlp/wo", l)
        h = h + project(m, lay["mlp"]["wo"][l], a_l, a_r, block=b,
                        quant=quant)
    return _norm(h, params["final_norm"], eps=arch.eps)


def head_stats(arch: Arch, params, hrows, want: np.ndarray,
               quant: Optional[str]) -> Dict[str, np.ndarray]:
    """Per row of ``hrows``: the largest logit (``best``), the logit of
    token ``want[row]`` (``wanted``) and the arg-max token (``arg``), over
    the real vocabulary."""
    w = params["lm_head"]["w"]
    edges = np.linspace(0, arch.vocab, VOCAB_BLOCKS + 1).astype(int)
    n = len(want)
    best = np.full(n, -np.inf)
    arg = np.zeros(n, np.int64)
    wanted = np.zeros(n)
    for lo, hi in zip(edges[:-1], edges[1:]):
        lg = np.asarray(head_block(hrows, w[:, lo:hi], quant=quant),
                        np.float64)
        top = lg.max(axis=1)
        upd = top > best
        arg[upd] = lo + lg.argmax(axis=1)[upd]
        best = np.maximum(best, top)
        inside = (want >= lo) & (want < hi)
        wanted[inside] = lg[np.nonzero(inside)[0], want[inside] - lo]
    return {"best": best, "wanted": wanted, "arg": arg}


def vocab_blocks(v: int) -> int:
    """Fewest equal vocabulary blocks, at least VOCAB_BLOCKS of them."""
    nb = VOCAB_BLOCKS
    while v % nb:
        nb += 1
    return nb


def pad_to(n: int, multiple: int = QUERY_BLOCK) -> int:
    return -(-n // multiple) * multiple


def served_gaps(arch: Arch, params, adapters: Dict[str, Any],
                served: Sequence[Tuple[np.ndarray, List[int], Optional[str]]],
                seq_pad: int, quant: Optional[str],
                controls: Sequence[str] = ()) -> Dict[str, np.ndarray]:
    """For each (prompt, served tokens, tenant): the gap by which each
    served token's logit lies below the reference's best at its position,
    all served tokens in one array under ``"program"``. For each of
    ``controls`` (quantizations below ``quant``), the same under its name
    for the token that the reference computed at that precision puts
    first at each of those positions."""
    out: Dict[str, List[np.ndarray]] = {"program": []}
    out.update({c: [] for c in controls})
    for prompt, toks_out, tenant in served:
        prompt = np.asarray(prompt, np.int32)
        toks_out = np.asarray(toks_out, np.int64)
        seq = np.concatenate([prompt, toks_out[:-1].astype(np.int32)])
        toks = np.zeros(seq_pad, np.int32)
        toks[:len(seq)] = seq
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks_out))
        ad = adapters.get(tenant) if tenant is not None else None
        h = hidden(arch, params, ad, toks, quant)[rows]
        st = head_stats(arch, params, h, toks_out, quant)
        out["program"].append(st["best"] - st["wanted"])
        for c in controls:
            hc = hidden(arch, params, ad, toks, c)[rows]
            carg = head_stats(arch, params, hc, toks_out, c)["arg"]
            at = head_stats(arch, params, h, carg, quant)["wanted"]
            out[c].append(st["best"] - at)
    return {k: (np.concatenate(v) if v else np.zeros(0))
            for k, v in out.items()}


# ---------------------------------------------------------------------------
# training: loss, adapter gradients, AdamW
# ---------------------------------------------------------------------------

def train_hidden(arch: Arch, params, adapter, tokens,
                 quant: Optional[str] = None):
    """Final-normed hidden states (S, d) of one row with the adapter's
    generators ({path: {"L", "R"}} (layers, r, b, b)) applied weight side.
    Each projection and attention block is recomputed in the backward
    pass, so only their inputs are kept."""
    lay = params["layers"]
    b = arch.block
    proj = jax.checkpoint(lambda x, w, a_l, a_r: project(
        x, w, a_l, a_r, block=b, quant=quant))
    attn = jax.checkpoint(lambda q, k, v: attention(q, k, v, arch=arch))
    h = jnp.take(params["embed"]["table"], tokens, axis=0).astype(
        jnp.float32)
    for l in range(arch.layers):
        def ad(path):
            return adapter[path]["L"][l], adapter[path]["R"][l]
        x = rms(h, lay["attn_norm"][l], arch.eps)
        qkv = []
        for name in ("wq", "wk", "wv"):
            y = proj(x, lay["attn"][name][l], *ad(f"layers/attn/{name}"))
            bias = lay["attn"].get("b" + name[1])
            if bias is not None:
                y = y + bias[l].astype(jnp.float32)
            qkv.append(y)
        h = h + proj(attn(*qkv), lay["attn"]["wo"][l], *ad("layers/attn/wo"))
        x = rms(h, lay["mlp_norm"][l], arch.eps)
        gate = proj(x, lay["mlp"]["wg"][l], *ad("layers/mlp/wg"))
        up = proj(x, lay["mlp"]["wi"][l], *ad("layers/mlp/wi"))
        h = h + proj(jax.nn.silu(gate) * up, lay["mlp"]["wo"][l],
                     *ad("layers/mlp/wo"))
    return rms(h, params["final_norm"], arch.eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head_stats(h, w, labels, lo, *, quant: Optional[str]):
    """Max, sum of exp(logit - max) and the label's logit (0 where the
    label is outside) of one vocabulary block."""
    lg = jnp.dot(h, dequant(w, quant), precision=HIGH)
    m = lg.max(axis=1)
    idx = labels - lo
    inside = (idx >= 0) & (idx < lg.shape[1])
    gold = jnp.take_along_axis(lg, jnp.clip(idx, 0, lg.shape[1] - 1)[:, None],
                               axis=1)[:, 0]
    return m, jnp.exp(lg - m[:, None]).sum(1), jnp.where(inside, gold, 0.0)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head_dh(h, w, labels, lo, lse, *, quant: Optional[str]):
    """This block's part of d(mean cross-entropy)/dh: (softmax - onehot)
    W^T over the rows."""
    wf = dequant(w, quant)
    lg = jnp.dot(h, wf, precision=HIGH)
    p = jnp.exp(lg - lse[:, None])
    onehot = (labels - lo)[:, None] == jnp.arange(lg.shape[1])[None, :]
    return jnp.dot(p - onehot, wf.T, precision=HIGH) / h.shape[0]


def head_loss_and_dh(arch: Arch, params, h, labels,
                     quant: Optional[str] = None):
    """Mean cross-entropy of one row and its gradient in h, a vocabulary
    block at a time, so the (S, V) logits never exist at once."""
    w = params["lm_head"]["w"]
    nb = vocab_blocks(arch.vocab)
    vb = arch.vocab // nb
    labels = jnp.asarray(labels)
    m = s = gold = None
    for i in range(nb):
        bm, bs, bg = _head_stats(h, w[:, i * vb:(i + 1) * vb], labels,
                                 i * vb, quant=quant)
        if m is None:
            m, s, gold = bm, bs, bg
        else:
            mn = jnp.maximum(m, bm)
            s = s * jnp.exp(m - mn) + bs * jnp.exp(bm - mn)
            m, gold = mn, gold + bg
    lse = m + jnp.log(s)
    dh = sum(_head_dh(h, w[:, i * vb:(i + 1) * vb], labels, i * vb, lse,
                      quant=quant) for i in range(nb))
    return jnp.mean(lse - gold), dh


def loss_and_grad(arch: Arch, params, adapter, tokens, labels,
                  quant: Optional[str] = None):
    """Mean next-token cross-entropy of a batch (rows, S) and its gradient
    in the adapter's generators: per row, the stack's pullback of the
    head's gradient."""
    rows = tokens.shape[0]
    total, grads = 0.0, None
    for r in range(rows):
        h, pullback = jax.vjp(
            lambda a: train_hidden(arch, params, a, tokens[r], quant),
            adapter)
        loss, dh = head_loss_and_dh(arch, params, h, labels[r], quant)
        (g,) = pullback(dh / rows)
        total = total + float(loss) / rows
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, grads


def adamw(opt: Dict[str, float], grads, state, theta):
    """One AdamW step as the configuration states it: the gradient clipped
    to a global norm, bias-corrected moments, decoupled weight decay on
    every adapter matrix. Returns (theta, state, clipped gradient)."""
    leaves = jax.tree.leaves(grads)
    gn = float(np.sqrt(sum(float(jnp.sum(g * g)) for g in leaves)))
    scale = min(1.0, opt["grad_clip"] / max(gn, 1e-12))
    g = jax.tree.map(lambda x: x * scale, grads)
    t = state["t"] + 1
    mu = jax.tree.map(lambda m, x: opt["b1"] * m + (1 - opt["b1"]) * x,
                      state["mu"], g)
    nu = jax.tree.map(lambda v, x: opt["b2"] * v + (1 - opt["b2"]) * x * x,
                      state["nu"], g)
    bc1, bc2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
    theta = jax.tree.map(
        lambda p, m, v: p - opt["learning_rate"] * (
            (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
            + opt["weight_decay"] * p), theta, mu, nu)
    return theta, {"t": t, "mu": mu, "nu": nu}, g


def train_steps(arch: Arch, params, theta0, batches, opt: Dict[str, float],
                quant: Optional[str] = None):
    """The first len(batches) steps: each step's loss, the clipped first
    gradient, and the adapter after the last step (base weights
    quantized as ``quant`` says when given: the control)."""
    theta = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), theta0)
    zeros = jax.tree.map(jnp.zeros_like, theta)
    state = {"t": 0, "mu": zeros, "nu": zeros}
    losses, first = [], None
    for tokens, labels in batches:
        loss, grads = loss_and_grad(arch, params, theta, jnp.asarray(tokens),
                                    jnp.asarray(labels), quant=quant)
        theta, state, g = adamw(opt, grads, state, theta)
        losses.append(float(loss))
        if first is None:
            first = g
    return losses, first, theta
