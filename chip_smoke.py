#!/usr/bin/env python3
"""Bring-up check: serve and fine-tune qwen2-72b on a TPU through the
entry points a user calls.

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # the four-chip phase only

The model is qwen2-72b at its published widths — d_model 8192, 64 query and
8 kv heads of 128, d_ff 29568, vocab 152064, bf16 — with ``num_layers`` cut
from 80 to 2 (8.49 GB of parameters). Weights, adapters, prompts and
training data are made from seeds; nothing is read from outside the
checkout.

One chip runs these phases in order:

  device        the first device must be a TPU; otherwise exit non-zero
                before anything is built
  serve-ref     ModelRuntime on the reference path with a 6-tenant
                gsoft/boft/householder bank (block 32); 8 mixed-length
                requests through PagedServeEngine behind EngineCluster
  serve-pallas  the same params, bank and requests with use_pallas=True;
                prefill and teacher-forced decode logits against
                serve-ref, and the decode step must hold Pallas kernels
  serve-int8    int8 base weights under a gsoft bank, the Pallas path
                against the reference path (each int8 tree built, used
                and freed in turn)
  train         3 GSOFT steps through repro.train.loop.train with
                use_pallas=True, batch 1, seq 256, against the same steps
                on the reference path: losses, gradient norms, and each
                adapter leaf's optimizer moment

``--chips 4`` runs paged serving (reference path, same bank) at tp=4 and as
four one-chip replicas behind EngineCluster, each compared with a one-chip
run in this process, and prints what each device holds.

Each phase prints one line: what ran, compile and wall seconds, device 0's
``peak_bytes_in_use``, and every comparison with its largest difference
and its tolerance. The last line of standard output is
``{"ok": true, "device": {...}}``; any failure raises, exiting non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

ARCH = "qwen2-72b"
LAYERS = 2                   # cut from 80; widths are the published ones
BLOCK = 32                   # GS / OFT block size of the bank adapters
TENANTS = 6
N_REQ = 8
PROMPT_LEN = (8, 48)         # mixed prompt lengths, U[lo, hi]
MAX_NEW = (4, 8)             # mixed token budgets, U[lo, hi]
MAX_LEN = 64
PAGE = 16
CHUNK = 32                   # prefill chunk (tokens per tick)
DECODE_STEPS = 4             # teacher-forced decode steps compared
# Logits of two execution paths agree when max |a - b| <= LOGIT_RTOL *
# max |reference logits|: bf16 activations round at different points on
# the kernel and reference paths, and int8 adds no new rounding between
# them (both paths read the same codes).
LOGIT_RTOL = 0.03
# Training, kernel path against reference path over the same steps: each
# step's loss (nats), each step's gradient global norm (relative), and each
# adapter leaf's first moment after the last step (max |diff| relative to
# the leaf's max |moment|). On one v5e the gaps were 6.8e-4 nats (step 2),
# 9.4e-5 and 1.07e-2 (attn/wq/R); the bounds are about 3x those.
LOSS_ATOL = 2e-3
GNORM_RTOL = 3e-4
MOMENT_RTOL = 0.03


class PhaseError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseError(what)


def model_config(use_pallas: bool = False):
    from repro.config import get_config
    return get_config(ARCH).with_overrides(num_layers=LAYERS,
                                           use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# building blocks: bank, traffic, serving, teacher-forced logits
# ---------------------------------------------------------------------------

def attach_bank(rt, methods, seed: int = 1):
    """``rt`` with a TENANTS-adapter eager bank; methods round-robin over
    the tenants. Returns (runtime, tenant names)."""
    from repro.core import peft as peft_lib
    from repro.launch.serve import make_demo_adapters
    names = [f"t{i}" for i in range(TENANTS)]
    peft = {n: peft_lib.PEFTConfig(method=methods[i % len(methods)],
                                   block_size=BLOCK,
                                   use_pallas=rt.cfg.use_pallas)
            for i, n in enumerate(names)}
    return rt.attach(make_demo_adapters(names, rt.params, peft, seed=seed),
                     peft), names


def make_requests(cfg, tenants, seed: int = 0):
    """N_REQ mixed-length requests; adapters round-robin over the tenants
    and the base model."""
    rng = np.random.default_rng(seed)
    adapters = list(tenants) + [None]
    return [{"prompt": rng.integers(1, min(cfg.vocab_size, 255), size=int(
                 rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))).tolist(),
             "max_new_tokens": int(rng.integers(MAX_NEW[0], MAX_NEW[1] + 1)),
             "adapter": adapters[i % len(adapters)]}
            for i in range(N_REQ)]


def serve(runtimes, requests):
    """Serve ``requests`` through one PagedServeEngine per runtime behind
    an EngineCluster; returns each request's tokens, checked for length
    and range."""
    from repro.distrib import EngineCluster
    from repro.serve.engine import PagedServeEngine
    eng = EngineCluster([PagedServeEngine(rt, max_batch=N_REQ,
                                          max_len=MAX_LEN, eos_id=-1,
                                          page_size=PAGE,
                                          prefill_chunk=CHUNK)
                         for rt in runtimes])
    rids = [eng.add_request(**r) for r in requests]
    res = eng.run()
    outs = [res[rid] for rid in rids]
    vocab = runtimes[0].cfg.vocab_size
    for r, out in zip(requests, outs):
        check(len(out) == r["max_new_tokens"]
              and all(0 <= t < vocab for t in out),
              f"bad completion {out} for budget {r['max_new_tokens']}")
    return outs


def _shapes(tree):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), tree)


def forced_logits(rt, requests, feed=None):
    """Teacher-forced logits through the paged serving closures: one
    request per slot, its prompt fed in CHUNK-token pieces, then
    DECODE_STEPS batched decode steps. ``feed`` (DECODE_STEPS, B) are the
    tokens fed at each step; None feeds this runtime's own greedy tokens.

    Returns (logits (1 + DECODE_STEPS, B, V) f32 — row 0 is the prefill's
    last-token logits — the tokens fed, and the shapes of the last decode
    call's arguments, for lowering the step)."""
    import jax.numpy as jnp
    from repro.core.peft import PrefillRequest
    b = len(requests)
    max_pages = -(-MAX_LEN // PAGE)
    state = rt.paged_state(b, b * max_pages + 1, PAGE, max_pages)
    table = np.zeros((b, max_pages + 1), np.int32)     # last col: garbage
    table[:, :max_pages] = 1 + np.arange(b * max_pages).reshape(b, -1)
    state["table"] = jnp.asarray(table)
    slots = [rt.slot(r["adapter"]) for r in requests]
    chunk = rt.chunk_prefill_fn()
    rows = []
    for i, r in enumerate(requests):
        prompt = r["prompt"]
        for start in range(0, len(prompt), CHUNK):
            end = min(start + CHUNK, len(prompt))
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :end - start] = prompt[start:end]
            req = PrefillRequest(
                batch={"tokens": jnp.asarray(toks)},
                last_idx=jnp.asarray(end - start - 1, jnp.int32),
                ctx=rt.context([slots[i]]))
            _, row, state = chunk(rt.params, req, state,
                                  jnp.asarray(i, jnp.int32),
                                  jnp.asarray(start, jnp.int32))
        rows.append(row)
    logits = [np.asarray(jnp.stack(rows), np.float32)]
    pos = np.asarray([len(r["prompt"]) for r in requests], np.int32)
    ctx = rt.context(slots)
    decode = rt.paged_decode_fn()
    fed = []
    for s in range(DECODE_STEPS):
        tok = (feed[s] if feed is not None
               else logits[-1].argmax(-1).astype(np.int32))
        fed.append(tok)
        args = (rt.params, ctx, jnp.asarray(tok[:, None]), state,
                jnp.asarray(pos))
        arg_shapes = _shapes(args)
        _, out, state = decode(*args)
        logits.append(np.asarray(out[:, -1], np.float32))
        pos = pos + 1
    return np.stack(logits), np.stack(fed), arg_shapes


def compare_logits(got, want, label: str):
    """Largest logit difference against LOGIT_RTOL of the reference scale;
    greedy tokens must agree wherever the reference's top-2 margin
    exceeds twice the tolerance. Returns the comparison record."""
    err = float(np.abs(got - want).max())
    tol = LOGIT_RTOL * float(np.abs(want).max())
    top2 = np.sort(want, axis=-1)[..., -2:]
    decisive = (top2[..., 1] - top2[..., 0]) > 2 * tol
    agree = got.argmax(-1)[decisive] == want.argmax(-1)[decisive]
    rec = {"max_abs_diff": err, "tol": tol, "rtol": LOGIT_RTOL,
           "finite": bool(np.isfinite(got).all()),
           "decisive_tokens": int(decisive.sum()),
           "decisive_equal": int(agree.sum()), "rows": int(decisive.size)}
    check(rec["finite"], f"{label}: non-finite logits")
    check(err <= tol, f"{label}: logits differ by {err:.4g} > {tol:.4g}")
    check(bool(agree.all()), f"{label}: {int((~agree).sum())} decisive "
          "greedy tokens differ")
    return rec


def kernel_calls(rt, arg_shapes) -> int:
    """tpu_custom_call sites (Pallas kernels) in the compiled paged decode
    step."""
    text = rt.paged_decode_fn().lower(*arg_shapes).compile().as_text()
    return text.count("tpu_custom_call")


# ---------------------------------------------------------------------------
# phases (the functions tests call at the smoke config)
# ---------------------------------------------------------------------------

MIXED = ("gsoft", "boft", "householder")


def phase_serve_ref(cfg, params):
    """Reference path: serve the requests, record teacher-forced logits."""
    from repro.core.runtime import ModelRuntime
    rt, tenants = attach_bank(ModelRuntime(cfg.with_overrides(
        use_pallas=False), params), MIXED)
    requests = make_requests(cfg, tenants)
    tokens = serve([rt], requests)
    logits, feed, _ = forced_logits(rt, requests)
    return {"requests": requests, "tokens": tokens, "logits": logits,
            "feed": feed}, {
        "ran": f"{len(requests)} requests, {sum(map(len, tokens))} tokens, "
               f"bank {TENANTS}x{'/'.join(MIXED)} block {BLOCK}",
        "logits_finite": bool(np.isfinite(logits).all())}


def phase_serve_pallas(cfg, params, ref, min_kernels: int = 1):
    """Kernel path on the same params, bank and requests."""
    from repro.core.runtime import ModelRuntime
    rt, _ = attach_bank(ModelRuntime(cfg.with_overrides(use_pallas=True),
                                     params), MIXED)
    tokens = serve([rt], ref["requests"])
    logits, _, shapes = forced_logits(rt, ref["requests"], ref["feed"])
    n = kernel_calls(rt, shapes)
    check(n >= min_kernels, f"decode step holds {n} Pallas kernels")
    same = sum(a == b for a, b in zip(tokens, ref["tokens"]))
    return {"ran": f"{len(tokens)} requests, {sum(map(len, tokens))} "
                   f"tokens; free-running completions equal to serve-ref: "
                   f"{same}/{len(tokens)}",
            "tpu_custom_call": n,
            "logits_vs_ref": compare_logits(logits, ref["logits"],
                                            "serve-pallas")}


def phase_serve_int8(cfg, params, min_kernels: int = 1):
    """int8 base weights under a gsoft bank: reference path, then the
    Pallas path (gs_q_matmul_banked / q_matmul) against it."""
    from repro.core.runtime import ModelRuntime
    out, ref = {}, None
    for use_pallas in (False, True):
        rt, tenants = attach_bank(ModelRuntime(cfg.with_overrides(
            use_pallas=use_pallas), params), ("gsoft",))
        rt = rt.quantized("int8")
        requests = make_requests(cfg, tenants)
        tokens = serve([rt], requests)
        logits, feed, shapes = forced_logits(
            rt, requests, None if ref is None else ref[1])
        if ref is None:
            ref = (logits, feed)
        else:
            n = kernel_calls(rt, shapes)
            check(n >= min_kernels, f"int8 decode step holds {n} kernels")
            out["tpu_custom_call"] = n
            out["logits_vs_ref"] = compare_logits(logits, ref[0],
                                                  "serve-int8")
        out[f"tokens_{'pallas' if use_pallas else 'ref'}"] = sum(
            map(len, tokens))
        del rt                       # free this int8 tree before the next
    out["ran"] = f"{N_REQ} requests twice over int8 weights, gsoft bank"
    return out


def phase_train(cfg, steps: int = 3, seq: int = 256, batch: int = 1):
    """GSOFT fine-tuning through the custom-VJP kernels against the
    reference path from the same seeds, step by step: the loss, the
    gradient's global norm, and per adapter leaf the optimizer's first
    moment after the last step (a running mean of that leaf's gradients,
    so the kernels' backward is compared leaf by leaf)."""
    import jax
    from repro import optim
    from repro.core import peft as peft_lib
    from repro.data import DataConfig
    from repro.optim import schedules
    from repro.train.loop import LoopConfig, train
    from repro.train.steps import TrainStepConfig

    def run(use_pallas: bool):
        c = cfg.with_overrides(use_pallas=use_pallas)
        tcfg = TrainStepConfig(
            peft=peft_lib.PEFTConfig(method="gsoft", block_size=BLOCK,
                                     use_pallas=use_pallas),
            opt=optim.OptimizerConfig(learning_rate=1e-3),
            schedule=schedules.warmup_cosine(0, steps))
        dcfg = DataConfig(seq_len=seq, global_batch=batch, seed=0,
                          vocab_size=min(c.vocab_size, 256))
        out = train(c, tcfg, dcfg, LoopConfig(steps=steps, log_every=1),
                    resume=False, log_fn=lambda _: None)
        mu = jax.device_get(peft_lib.flatten_paths(out["opt_state"]["mu"]))
        return out["history"], mu        # the params in ``out`` go here

    got, mu = run(True)
    losses = [h["loss"] for h in got]
    check(len(got) == steps and all(math.isfinite(h[k]) for h in got
                                    for k in ("loss", "grad_norm")),
          f"non-finite training step {got}")
    want, mu_ref = run(False)
    loss_err = max(abs(a["loss"] - b["loss"]) for a, b in zip(got, want))
    norm_err = max(abs(a["grad_norm"] / b["grad_norm"] - 1)
                   for a, b in zip(got, want))
    leaf_err = {p: float(np.abs(mu[p] - r).max() / np.abs(r).max())
                for p, r in mu_ref.items()}
    worst = max(leaf_err, key=leaf_err.get)
    rec = {"ran": f"{steps} GSOFT steps, batch {batch}, seq {seq}, "
                  "kernel and reference paths",
           "losses": losses, "ref_losses": [h["loss"] for h in want],
           "grad_norms": [h["grad_norm"] for h in got],
           "ref_grad_norms": [h["grad_norm"] for h in want],
           "loss_max_abs_diff": loss_err, "loss_tol": LOSS_ATOL,
           "grad_norm_max_rel_diff": norm_err, "grad_norm_tol": GNORM_RTOL,
           "moment_worst_leaf": worst,
           "moment_max_rel_diff": leaf_err[worst], "moment_tol": MOMENT_RTOL}
    check(loss_err <= LOSS_ATOL, f"losses {losses} vs reference "
          f"{rec['ref_losses']} (|diff| {loss_err:.3g} > {LOSS_ATOL})")
    check(norm_err <= GNORM_RTOL, f"gradient norms {rec['grad_norms']} vs "
          f"reference {rec['ref_grad_norms']}")
    check(leaf_err[worst] <= MOMENT_RTOL, f"first moment of {worst} "
          f"differs by {leaf_err[worst]:.3g} of its scale")
    return rec


def phase_four_chips(cfg, params):
    """Paged serving (reference path) at tp=4 and as four one-chip
    replicas, each compared with a one-chip run in this process."""
    import jax
    from repro.core.runtime import ModelRuntime
    from repro.distrib import serve_mesh
    from repro.launch.serve import replica_runtimes
    cfg = cfg.with_overrides(use_pallas=False)
    one, tenants = attach_bank(ModelRuntime(cfg, params), MIXED)
    requests = make_requests(cfg, tenants)
    tokens = serve([one], requests)
    want, feed, _ = forced_logits(one, requests)
    out = {}
    tp, _ = attach_bank(ModelRuntime(cfg, params, mesh=serve_mesh(4)),
                        MIXED)
    got, _, _ = forced_logits(tp, requests, feed)
    out["tp4_vs_one_chip"] = compare_logits(got, want, "tp=4")
    out["tp4_completions_equal"] = sum(
        a == b for a, b in zip(serve([tp], requests), tokens))
    del tp
    reps = replica_runtimes(one, 4)
    homes = [jax.tree.leaves(r.params)[0].sharding.device_set for r in reps]
    check(len(set().union(*homes)) == 4, f"replicas share devices: {homes}")
    out["replica_completions_equal"] = sum(
        a == b for a, b in zip(serve(reps, requests), tokens))
    for i, r in enumerate(reps):
        got, _, _ = forced_logits(r, requests, feed)
        out[f"replica{i}_vs_one_chip"] = compare_logits(
            got, want, f"replica {i}")
    out["bytes_in_use_per_device"] = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]
    out["ran"] = (f"{len(requests)} requests at tp=4 and on 4 replicas "
                  "(one per chip)")
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums XLA backend-compile seconds from JAX's monitoring events (a
    compile served from the persistent cache emits none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.total += secs
        jax.monitoring.register_event_duration_secs_listener(listen)


def run_phase(name: str, clock: CompileClock, fn, *args):
    """Run one phase and print its line; a failure propagates. ``fn``
    returns its record, or (value, record)."""
    import jax
    c0, t0 = clock.total, time.perf_counter()
    result = fn(*args)
    rec = result[1] if isinstance(result, tuple) else result
    stats = jax.devices()[0].memory_stats() or {}
    line = {"compile_s": round(clock.total - c0, 3),
            "wall_s": round(time.perf_counter() - t0, 3),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"), **rec}
    print(f"[{name}] ok {json.dumps(line)}", flush=True)
    return result


def build_params(cfg):
    import jax
    from repro.core.runtime import ModelRuntime
    params = ModelRuntime(cfg, key=jax.random.PRNGKey(0)).params
    jax.block_until_ready(params)
    return params, {"ran": f"{ARCH} widths, {LAYERS} of 80 layers, seed 0"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "visible", file=sys.stderr)
        return 1
    from repro.launch import compile_cache      # fails outside a checkout
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"[device] ok {json.dumps(device)}", flush=True)
    compile_cache.enable()
    clock = CompileClock()
    cfg = model_config()
    params, _ = run_phase("params", clock, build_params, cfg)
    if args.chips == 4:
        run_phase("four-chips", clock, phase_four_chips, cfg, params)
    else:
        ref, _ = run_phase("serve-ref", clock, phase_serve_ref, cfg, params)
        run_phase("serve-pallas", clock, phase_serve_pallas, cfg, params,
                  ref)
        run_phase("serve-int8", clock, phase_serve_int8, cfg, params)
        del params, ref              # the train phase builds its own
        run_phase("train", clock, phase_train, cfg)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    sys.exit(main())
