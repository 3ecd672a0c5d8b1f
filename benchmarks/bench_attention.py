"""Micro-benchmark: Pallas flash attention vs naive XLA attention.

Times ops/flash_attention.py fwd and fwd+bwd against the O(S^2)-in-HBM
XLA attention across sequence lengths on the current backend, plus one
BERT-MLM train-step throughput line (BASELINE config 4's hot path). This
is the on-chip evidence for routing models/bert.py through the flash
kernels; re-run when tuning block sizes or the dispatch threshold.

Usage: python benchmarks/bench_attention.py [--batch 8] [--heads 8]
           [--head-dim 64] [--seqs 512,1024,2048,4096] [--iters 10]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import timeit

sys.path.insert(0,
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
from ray_shuffling_data_loader_tpu.ops import on_tpu
from ray_shuffling_data_loader_tpu.utils.compile_cache import (
    enable_compile_cache)


def _time(step_fn, iters=10):
    """Mean wall time of one ``step_fn(key) -> pytree`` call.

    ``step_fn`` is jitted and draws its inputs from the key, so every
    timed call works on fresh data. The calls are dispatched back to back
    and the clock stops after ``block_until_ready`` on the last result:
    the device runs them in order, so that wait covers all of them.
    """
    jax.block_until_ready(step_fn(jax.random.key(7)))  # compile + warm
    keys = jax.random.split(jax.random.key(13), iters)
    start = timeit.default_timer()
    for key in keys:
        out = step_fn(key)
    jax.block_until_ready(out)
    return (timeit.default_timer() - start) / iters


def naive_attention(q, k, v):
    """Reference XLA attention: full (B, H, S, S) scores in HBM."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def check_correctness(flash, seq_len: int, b: int, h: int, d: int,
                      fwd_tol: float = 2e-2, grad_tol: float = 2e-2) -> None:
    """On-chip correctness gate: max-abs-error of the compiled flash
    fwd AND bwd against the fp32 XLA reference, asserted, not just
    printed.

    The interpret-mode pytest suite proves the algorithm; this proves
    the MOSAIC-COMPILED kernel's numerics on the real device (bf16
    inputs, fp32 accumulation — tolerance matches the bf16 resolution
    bound the interpret tests use for bf16 inputs,
    tests/test_flash_attention.py). Errors are computed on device and
    fetched as scalars.
    """
    shape = (b, h, seq_len, d)
    kq, kk, kv = jax.random.split(jax.random.key(42), 3)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    @jax.jit
    def errors(q, k, v):
        out_f = flash(q, k, v).astype(jnp.float32)
        out_r = naive_attention(q, k, v)
        fwd_err = jnp.max(jnp.abs(out_f - out_r))
        # Grads of a non-trivial scalar (weighted sum keeps the cotangent
        # dense and non-uniform) through both implementations.
        w = jax.random.normal(jax.random.key(7), shape, jnp.float32)

        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

        gf = jax.grad(functools.partial(loss, flash), (0, 1, 2))(q, k, v)
        gr = jax.grad(functools.partial(loss, naive_attention),
                      (0, 1, 2))(q, k, v)
        grad_err = jnp.max(jnp.asarray(
            [jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
             for a, b in zip(gf, gr)]))
        return fwd_err, grad_err

    fwd_err, grad_err = (float(x) for x in errors(q, k, v))
    print(f"S={seq_len:>5}  correctness: max|flash-xla| fwd {fwd_err:.3e} "
          f"(tol {fwd_tol:.0e}), grad {grad_err:.3e} (tol {grad_tol:.0e})")
    assert fwd_err <= fwd_tol, (
        f"flash fwd diverges from XLA reference on this backend: "
        f"{fwd_err} > {fwd_tol}")
    assert grad_err <= grad_tol, (
        f"flash bwd diverges from XLA reference on this backend: "
        f"{grad_err} > {grad_tol}")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--seqs", type=str, default="512,1024,2048,4096")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--cpu", action="store_true",
                        help="pin the CPU backend (smoke runs)")
    parser.add_argument("--skip-bert", action="store_true")
    parser.add_argument("--skip-correctness", action="store_true",
                        help="skip the on-chip max-error gate (it runs "
                             "before any timing by default)")
    args = parser.parse_args()

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()

    interpret = not on_tpu()
    print(f"backend={jax.default_backend()} interpret={interpret} "
          f"batch={args.batch} heads={args.heads} head_dim={args.head_dim}")
    rng = np.random.default_rng(0)
    b, h, d = args.batch, args.heads, args.head_dim

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, interpret=interpret)

    seqs = list(map(int, args.seqs.split(",")))
    if not args.skip_correctness:
        # Gate timings on numerics: the compiled kernel must match the
        # XLA reference on THIS backend before its speed means anything.
        # The largest S bounds accumulation-order divergence; S=512 also
        # covers the multi-block fwd path at small shapes. Capped at 4096:
        # the gate's naive fwd+bwd reference materializes (B,H,S,S) fp32
        # scores, which OOMs beyond that — the very regime flash exists
        # for, so long-S runs gate at the cap and time beyond it.
        gate_cap = 4096
        for s in sorted({min(seqs[0], gate_cap), min(seqs[-1], gate_cap)}):
            check_correctness(flash, s, b, h, d)

    for s in seqs:
        shape = (b, h, s, d)

        def gen(key):
            kq, kk, kv = jax.random.split(key, 3)
            return (jax.random.normal(kq, shape, jnp.bfloat16),
                    jax.random.normal(kk, shape, jnp.bfloat16),
                    jax.random.normal(kv, shape, jnp.bfloat16))

        def fwd_step(key, attn):
            q, k, v = gen(key)
            return attn(q, k, v).sum()

        def fb_step(key, attn):
            q, k, v = gen(key)
            loss, grads = jax.value_and_grad(
                lambda q, k, v: attn(q, k, v).sum(), (0, 1, 2))(q, k, v)
            return loss, jax.tree.map(lambda g: g.sum(), grads)

        naive_f = jax.jit(functools.partial(fwd_step, attn=naive_attention))
        flash_f = jax.jit(functools.partial(fwd_step, attn=flash))
        naive_g = jax.jit(functools.partial(fb_step, attn=naive_attention))
        flash_g = jax.jit(functools.partial(fb_step, attn=flash))

        # FLOPs: 2 matmuls of 2*B*H*S*S*D each (fwd); f+b ~3.5x fwd.
        flops = 4 * b * h * s * s * d
        row = [f"S={s:>5}"]
        try:
            t_n = _time(naive_f, iters=args.iters)
            row.append(f"xla fwd {t_n*1e3:8.2f}ms "
                       f"{flops/t_n/1e12:6.2f}TF/s")
        except Exception as e:  # noqa: BLE001 - OOM at long S is the point
            t_n = None
            row.append(f"xla fwd FAILED ({type(e).__name__})")
        t_f = _time(flash_f, iters=args.iters)
        row.append(f"flash fwd {t_f*1e3:8.2f}ms {flops/t_f/1e12:6.2f}TF/s")
        if t_n:
            row.append(f"speedup {t_n/t_f:5.2f}x")
        try:
            t_ng = _time(naive_g, iters=args.iters)
            row.append(f"| xla f+b {t_ng*1e3:8.2f}ms")
        except Exception as e:  # noqa: BLE001
            t_ng = None
            row.append(f"| xla f+b FAILED ({type(e).__name__})")
        t_fg = _time(flash_g, iters=args.iters)
        row.append(f"flash f+b {t_fg*1e3:8.2f}ms")
        if t_ng:
            row.append(f"speedup {t_ng/t_fg:5.2f}x")
        print("  ".join(row))

    if args.skip_bert:
        return

    # BERT-MLM train step (models/bert.py), flash vs inline attention.
    import optax
    from ray_shuffling_data_loader_tpu.models import bert

    seq_len = 512
    cfg = bert.BertConfig(vocab_size=30522, hidden_dim=512, num_layers=4,
                          num_heads=8, ffn_dim=2048, max_seq_len=seq_len)
    params = bert.init(cfg, jax.random.key(0))
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, seq_len)), jnp.int32)
    targets = jnp.where(
        jnp.asarray(rng.random((args.batch, seq_len))) < 0.15, tokens,
        bert.IGNORE_ID).astype(jnp.int32)
    tx = optax.adam(1e-4)

    flash_fn = fa.make_flash_attention_fn()

    for name, attention_fn in (("inline", None), ("flash", flash_fn)):
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, tokens, targets, _fn=attention_fn):
            loss, grads = jax.value_and_grad(bert.loss_fn, argnums=1)(
                cfg, params, tokens, targets, attention_fn=_fn)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        p, o, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready(loss)  # compile + warm
        start = timeit.default_timer()
        iters = max(3, args.iters // 2)
        for _ in range(iters):
            p, o, loss = step(p, o, tokens, targets)
        # The final loss depends on every prior step's params, so waiting
        # for it waits for the whole chain.
        jax.block_until_ready(loss)
        dt = (timeit.default_timer() - start) / iters
        print(f"bert[{name:6}] S={seq_len} train step {dt*1e3:8.2f}ms  "
              f"{args.batch*seq_len/dt:,.0f} tokens/s  loss={float(loss):.3f}")


if __name__ == "__main__":
    main()
