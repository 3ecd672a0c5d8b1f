"""Sequence/context-parallel attention: ring attention and Ulysses.

The reference has no sequence axis at all (SURVEY.md §2.4/§5: tabular rows;
its BERT workload batches pre-tokenized fixed-length rows) — long-context
scaling is a capability the TPU build adds, designed mesh-first rather than
ported: both strategies are pure XLA collectives (``ppermute`` /
``all_to_all``) inside ``shard_map`` over a sequence mesh axis, so they run
over ICI on a slice and over DCN across slices with no custom kernels or
NCCL-style backend.

Two interchangeable strategies, both computing exact (not approximate)
softmax attention for sequences sharded along a mesh axis:

- :func:`ring_self_attention` — blockwise online-softmax attention; K/V
  (and the key-side bias) rotate around the ring one hop per step, so no
  device ever materializes the full sequence or the full score matrix.
  Memory per device: O(S/n · S/n) scores, O(S/n) K/V. The classic ring
  attention construction (Liu et al., 2023), built from ``jax.lax.ppermute``.
- :func:`ulysses_attention` — all-to-all head parallelism (DeepSpeed
  Ulysses): one ``all_to_all`` reshards from sequence-sharded to
  head-sharded, each device runs full-sequence attention for H/n heads,
  and a second ``all_to_all`` reshards back. Cheaper collectives for
  moderate S (2 all-to-alls vs n-1 ppermutes) but requires n | H and
  materializes full-length K/V per device.

Numerics: scores and the softmax accumulators are float32 regardless of
input dtype (bf16 in the models); masking uses a large finite negative so
fully-masked rows stay NaN-free.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_shuffling_data_loader_tpu.ops import on_tpu

NEG_INF = -1e9  # finite, like models/bert.py — keeps softmax NaN-free


def _shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off — the attention
    bodies run collectives whose replication the checker cannot infer."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_ACC_MIN = -1e30


def causal_bias(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """(1, 1, Sq, Sk) additive causal mask from global position vectors."""
    return jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                     NEG_INF)[None, None, :, :].astype(jnp.float32)


def _block_attention(q, k, v, bias, m, l, o):
    """One online-softmax accumulation step against a K/V block.

    q: (B, H, Sq, D) f32 (pre-scaled); k/v: (B, H, Sk, D); bias
    broadcastable to (B, H, Sq, Sk) f32. Carries m (running max), l
    (running denominator) of shape (B, H, Sq) and o (B, H, Sq, D), all f32.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k.astype(jnp.float32))
    if bias is not None:
        scores = scores + bias
    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, o_new


def _ring_attention_fwd_impl(axis_name: str, causal: bool, q, k, v, kv_bias):
    """Forward ring pass; returns (out, lse) with lse = per-query
    logsumexp (B, H, Sq) — the residual that makes the recompute-per-hop
    backward pass possible without saving any per-step intermediate.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    sq, sk = q.shape[2], k.shape[2]
    d = q.shape[-1]
    qf = q.astype(jnp.float32) * (1.0 / jnp.sqrt(d).astype(jnp.float32))
    m0 = jnp.full(q.shape[:3], _ACC_MIN, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    o0 = jnp.zeros(q.shape, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_bias = kv_bias is not None  # static: shapes the loop carry

    def step(i, carry):
        k_c, v_c, bias_c, m, l, o = carry
        # After i rotations this device holds chunk (my - i) mod n.
        src = (my - i) % n

        def accumulate(m, l, o):
            bias = bias_c
            if causal:
                cb = causal_bias(my * sq + jnp.arange(sq),
                                 src * sk + jnp.arange(sk))
                bias = cb if bias is None else bias + cb
            return _block_attention(qf, k_c, v_c, bias, m, l, o)

        if causal:
            # Chunks strictly in this query chunk's future contribute
            # nothing — skip their attention FLOPs entirely (about half
            # the ring steps at large n). The ppermutes below still run
            # every step, keeping the loop collective-uniform.
            m, l, o = jax.lax.cond(src > my, lambda m, l, o: (m, l, o),
                                   accumulate, m, l, o)
        else:
            m, l, o = accumulate(m, l, o)
        # One hop: send our current chunk to the next device on the ring.
        # (The final iteration's hop returns chunks to their owners — one
        # redundant ppermute, kept so the loop body is collective-uniform.)
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        if has_bias:
            bias_c = jax.lax.ppermute(bias_c, axis_name, perm)
        return k_c, v_c, bias_c, m, l, o

    bias0 = kv_bias.astype(jnp.float32) if has_bias else None
    _, _, _, m, l, o = jax.lax.fori_loop(
        0, n, step, (k, v, bias0, m0, l0, o0))
    l_safe = jnp.maximum(l, 1e-30)
    out = (o / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


def _ring_attention_bwd_impl(axis_name: str, causal: bool, q, k, v, kv_bias,
                             out, lse, do):
    """Backward ring pass (recompute per hop, standard flash identities).

    dk/dv (and dbias) accumulators travel around the ring *with* their K/V
    chunks: after the loop's n rotations every gradient chunk is back at
    its owner. Per-step memory is O(Sq/n · Sk/n) — no residuals from the
    forward other than (out, lse).
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    sq, sk = q.shape[2], k.shape[2]
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    # delta_i = sum_d do_i * out_i (softmax-backward correction).
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (B, H, Sq)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_bias = kv_bias is not None

    def step(i, carry):
        k_c, v_c, bias_c, dk_c, dv_c, dbias_c, dq = carry
        src = (my - i) % n

        def accumulate(dk_c, dv_c, dbias_c, dq):
            kf, vf = k_c.astype(jnp.float32), v_c.astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
            if has_bias:
                s = s + bias_c
            if causal:
                s = s + causal_bias(my * sq + jnp.arange(sq),
                                    src * sk + jnp.arange(sk))[0]
            p = jnp.exp(s - lse[..., None])  # recomputed softmax weights
            dv_new = dv_c + jnp.einsum("bhqk,bhqd->bhkd", p, dof)
            dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
            ds = p * (dp - delta[..., None])
            dq_new = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
            dk_new = dk_c + jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
            dbias_new = dbias_c
            if has_bias:
                dbias_new = dbias_c + ds.sum(axis=(1, 2))[:, None, None, :]
            return dk_new, dv_new, dbias_new, dq_new

        if causal:
            dk_c, dv_c, dbias_c, dq = jax.lax.cond(
                src > my, lambda a, b, c, e: (a, b, c, e), accumulate,
                dk_c, dv_c, dbias_c, dq)
        else:
            dk_c, dv_c, dbias_c, dq = accumulate(dk_c, dv_c, dbias_c, dq)
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        dk_c = jax.lax.ppermute(dk_c, axis_name, perm)
        dv_c = jax.lax.ppermute(dv_c, axis_name, perm)
        if has_bias:
            bias_c = jax.lax.ppermute(bias_c, axis_name, perm)
            dbias_c = jax.lax.ppermute(dbias_c, axis_name, perm)
        return k_c, v_c, bias_c, dk_c, dv_c, dbias_c, dq

    bias0 = kv_bias.astype(jnp.float32) if has_bias else None
    dbias0 = jnp.zeros((q.shape[0], 1, 1, sk), jnp.float32) if has_bias \
        else None
    carry0 = (k, v, bias0, jnp.zeros(k.shape, jnp.float32),
              jnp.zeros(v.shape, jnp.float32), dbias0,
              jnp.zeros(q.shape, jnp.float32))
    _, _, _, dk, dv, dbias, dq = jax.lax.fori_loop(0, n, step, carry0)
    dbias_out = dbias.astype(kv_bias.dtype) if has_bias else None
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias_out)


def _ring_flash_fwd_impl(axis_name: str, block_q: int, block_k: int,
                         interpret: bool, q, k, v, kv_bias):
    """Forward ring pass where each hop's local attention runs the Pallas
    flash kernels (ops/flash_attention.py) instead of einsum — no per-hop
    O(Sq·Sk) score tensor even locally; per-hop partials merge by
    logsumexp. Non-causal only (the flash bias is key-side, which cannot
    express a q×k causal mask)."""
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa

    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_bias = kv_bias is not None
    o0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full(q.shape[:3], _ACC_MIN, jnp.float32)

    def step(i, carry):
        k_c, v_c, bias_c, o, lse = carry
        out_h, lse_h = fa.flash_forward(q, k_c, v_c, bias_c,
                                        block_q=block_q, block_k=block_k,
                                        interpret=interpret)
        lse_h = lse_h[..., 0]
        lse_new = jnp.logaddexp(lse, lse_h)
        # out_h is this hop's normalized partial; exp(lse_h - lse_new)
        # rescales it to the global softmax denominator.
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + out_h.astype(jnp.float32)
             * jnp.exp(lse_h - lse_new)[..., None])
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        if has_bias:
            bias_c = jax.lax.ppermute(bias_c, axis_name, perm)
        return k_c, v_c, bias_c, o, lse_new

    bias0 = kv_bias.astype(jnp.float32) if has_bias else None
    _, _, _, o, lse = jax.lax.fori_loop(0, n, step, (k, v, bias0, o0, lse0))
    return o.astype(q.dtype), lse


def _ring_flash_bwd_impl(axis_name: str, block_q: int, block_k: int,
                         interpret: bool, q, k, v, kv_bias, out, lse, do):
    """Backward ring pass through the flash backward kernels. The GLOBAL
    lse makes each hop's recomputed weights the global softmax restricted
    to that hop's keys, so per-hop kernel grads sum exactly; dk/dv
    accumulators ride the ring home with their chunks."""
    from ray_shuffling_data_loader_tpu.ops import flash_attention as fa

    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_bias = kv_bias is not None
    delta = fa._delta(do, out)      # once: every hop's queries are these

    def step(i, carry):
        k_c, v_c, bias_c, dk_c, dv_c, dbias_c, dq = carry
        dq_h, dk_h, dv_h, dbias_h = fa.flash_backward(
            q, k_c, v_c, bias_c, None, lse, do, block_q=block_q,
            block_k=block_k, interpret=interpret, delta=delta)
        dq = dq + dq_h.astype(jnp.float32)
        dk_c = dk_c + dk_h.astype(jnp.float32)
        dv_c = dv_c + dv_h.astype(jnp.float32)
        if has_bias:
            dbias_c = dbias_c + dbias_h.astype(jnp.float32)
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        dk_c = jax.lax.ppermute(dk_c, axis_name, perm)
        dv_c = jax.lax.ppermute(dv_c, axis_name, perm)
        if has_bias:
            bias_c = jax.lax.ppermute(bias_c, axis_name, perm)
            dbias_c = jax.lax.ppermute(dbias_c, axis_name, perm)
        return k_c, v_c, bias_c, dk_c, dv_c, dbias_c, dq

    bias0 = kv_bias.astype(jnp.float32) if has_bias else None
    dbias0 = (jnp.zeros((q.shape[0], 1, 1, k.shape[2]), jnp.float32)
              if has_bias else None)
    carry0 = (k, v, bias0, jnp.zeros(k.shape, jnp.float32),
              jnp.zeros(v.shape, jnp.float32), dbias0,
              jnp.zeros(q.shape, jnp.float32))
    _, _, _, dk, dv, dbias, dq = jax.lax.fori_loop(0, n, step, carry0)
    dbias_out = dbias.astype(kv_bias.dtype) if has_bias else None
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ring_flash_prim(axis_name: str, block_q: int, block_k: int,
                     interpret: bool, q, k, v, kv_bias):
    return _ring_flash_fwd_impl(axis_name, block_q, block_k, interpret,
                                q, k, v, kv_bias)[0]


def _ring_flash_prim_fwd(axis_name, block_q, block_k, interpret, q, k, v,
                         kv_bias):
    out, lse = _ring_flash_fwd_impl(axis_name, block_q, block_k, interpret,
                                    q, k, v, kv_bias)
    return out, (q, k, v, kv_bias, out, lse)


def _ring_flash_prim_bwd(axis_name, block_q, block_k, interpret, residuals,
                         do):
    q, k, v, kv_bias, out, lse = residuals
    return _ring_flash_bwd_impl(axis_name, block_q, block_k, interpret,
                                q, k, v, kv_bias, out, lse, do)


_ring_flash_prim.defvjp(_ring_flash_prim_fwd, _ring_flash_prim_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ring_attention_prim(axis_name: str, causal: bool, q, k, v, kv_bias):
    return _ring_attention_fwd_impl(axis_name, causal, q, k, v, kv_bias)[0]


def _ring_prim_fwd(axis_name, causal, q, k, v, kv_bias):
    out, lse = _ring_attention_fwd_impl(axis_name, causal, q, k, v, kv_bias)
    return out, (q, k, v, kv_bias, out, lse)


def _ring_prim_bwd(axis_name, causal, residuals, do):
    q, k, v, kv_bias, out, lse = residuals
    return _ring_attention_bwd_impl(axis_name, causal, q, k, v, kv_bias,
                                    out, lse, do)


_ring_attention_prim.defvjp(_ring_prim_fwd, _ring_prim_bwd)


def _ring_attention_shard(q, k, v, kv_bias, axis_name: str, causal: bool,
                          use_flash: bool = False,
                          block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          interpret: bool = False):
    """Per-shard ring attention body; must run under shard_map/pmap.

    Block defaults come from flash_attention's DEFAULT_BLOCK_Q/K (one
    retuning site); the kernel clamps blocks to the per-hop shard
    length, so small S/world shards compile exactly as before.

    q/k/v: (B, H, S_local, D) — this device's sequence chunk. kv_bias:
    (B, 1, 1, S_local) additive key-side bias or None. K/V (+bias) rotate
    around ``axis_name``; the local chunk's global offset is recovered from
    the ring step, which is what makes the causal mask correct.

    Differentiable via a custom VJP that reruns the ring (recompute per
    hop) instead of letting AD save every per-step O(S²/n²) intermediate.
    With ``use_flash`` each hop runs the Pallas flash kernels, removing
    even the per-hop local score tensor (non-causal only).
    """
    if use_flash:
        from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
        if block_q is None:
            block_q = fa.DEFAULT_BLOCK_Q
        if block_k is None:
            block_k = fa.DEFAULT_BLOCK_K
        return _ring_flash_prim(axis_name, block_q, block_k, interpret,
                                q, k, v, kv_bias)
    return _ring_attention_prim(axis_name, causal, q, k, v, kv_bias)


def _dispatch_sharded(shard_fn, q, k, v, bias, mesh: Mesh, seq_axis: str,
                      batch_axis: Optional[str]):
    """shard_map a per-shard attention body with the standard specs:
    q/k/v sequence-sharded on dim 2, bias (if any) on its key dim 3."""
    qkv_spec = P(batch_axis, None, seq_axis, None)
    bias_spec = P(batch_axis, None, None, seq_axis)
    if bias is None:
        fn = _shard_map(lambda q_, k_, v_: shard_fn(q_, k_, v_, None),
                        mesh=mesh, in_specs=(qkv_spec,) * 3,
                        out_specs=qkv_spec)
        return fn(q, k, v)
    fn = _shard_map(shard_fn, mesh=mesh,
                    in_specs=(qkv_spec,) * 3 + (bias_spec,),
                    out_specs=qkv_spec)
    return fn(q, k, v, bias)


def ring_self_attention(q: jax.Array,
                        k: jax.Array,
                        v: jax.Array,
                        mesh: Mesh,
                        seq_axis: str,
                        bias: Optional[jax.Array] = None,
                        batch_axis: Optional[str] = None,
                        causal: bool = False,
                        use_flash: Optional[bool] = None) -> jax.Array:
    """Exact attention over a sequence sharded on ``mesh[seq_axis]``.

    Args:
        q, k, v: (B, H, S, D) with S (globally) sharded over ``seq_axis``
            and optionally B over ``batch_axis``.
        bias: optional additive key-side bias (B, 1, 1, S) (e.g. padding
            mask as 0 / NEG_INF), sharded like the K sequence axis.
        causal: apply a causal mask using global positions.
        use_flash: run each hop's local attention through the Pallas flash
            kernels (no per-hop score tensor at all). ``None`` = auto: on
            for non-causal on a real TPU backend, off elsewhere. Explicit
            ``True`` off-TPU uses the (slow) Pallas interpreter — tests
            only. Causal + flash is rejected: the flash bias is key-side
            and cannot express a q×k causal mask.

    Returns (B, H, S, D), sharded like ``q``.
    """
    interpret = not on_tpu()
    if use_flash is None:
        use_flash = not causal and not interpret
    if use_flash and causal:
        raise ValueError(
            "use_flash=True does not support causal=True (key-side bias "
            "cannot express the causal mask); use the einsum ring path")
    shard_fn = functools.partial(_ring_attention_shard, axis_name=seq_axis,
                                 causal=causal, use_flash=use_flash,
                                 interpret=interpret)
    return _dispatch_sharded(shard_fn, q, k, v, bias, mesh, seq_axis,
                             batch_axis)


def _full_attention(q, k, v, bias):
    """Plain full-sequence attention (f32 softmax), used per Ulysses shard
    and as the reference implementation in tests."""
    d = q.shape[-1]
    qf = q.astype(jnp.float32) * (1.0 / jnp.sqrt(d).astype(jnp.float32))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32))
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights,
                     v.astype(jnp.float32))
    return out.astype(q.dtype)


def _ulysses_shard(q, k, v, kv_bias, axis_name: str, causal: bool,
                   use_flash: bool = False, interpret: bool = False):
    """Per-shard Ulysses body: seq-sharded -> head-sharded -> back."""
    # (B, H, S/n, D) -> (B, H/n, S, D): scatter heads, gather sequence.
    q = jax.lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                           tiled=True)
    k = jax.lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                           tiled=True)
    v = jax.lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                           tiled=True)
    bias = None
    if kv_bias is not None:
        # Key-side bias has no head dim to scatter — gather the full-length
        # bias on every device instead.
        bias = jax.lax.all_gather(kv_bias, axis_name, axis=3, tiled=True)
    if use_flash and not causal:
        from ray_shuffling_data_loader_tpu.ops import flash_attention as fa
        out = fa.flash_attention(q, k, v, bias, interpret=interpret)
    else:
        if causal:
            pos = jnp.arange(q.shape[2])
            cb = causal_bias(pos, pos)
            bias = cb if bias is None else bias + cb
        out = _full_attention(q, k, v, bias)
    # (B, H/n, S, D) -> (B, H, S/n, D): back to sequence-sharded.
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_attention(q: jax.Array,
                      k: jax.Array,
                      v: jax.Array,
                      mesh: Mesh,
                      seq_axis: str,
                      bias: Optional[jax.Array] = None,
                      batch_axis: Optional[str] = None,
                      causal: bool = False,
                      use_flash: Optional[bool] = None) -> jax.Array:
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism.

    Same contract as :func:`ring_self_attention`; additionally requires the
    head count be divisible by the ``seq_axis`` size. ``use_flash`` runs
    the per-shard full-sequence attention through the Pallas flash kernels
    (non-causal only; ``None`` = auto-on for non-causal on real TPUs).
    """
    n = mesh.shape[seq_axis]
    if q.shape[1] % n != 0:
        raise ValueError(
            f"ulysses_attention needs num_heads ({q.shape[1]}) divisible by "
            f"mesh axis '{seq_axis}' size ({n})")
    interpret = not on_tpu()
    if use_flash is None:
        use_flash = not causal and not interpret
    if use_flash and causal:
        raise ValueError(
            "use_flash=True does not support causal=True (key-side bias "
            "cannot express the causal mask)")
    shard_fn = functools.partial(_ulysses_shard, axis_name=seq_axis,
                                 causal=causal, use_flash=use_flash,
                                 interpret=interpret)
    return _dispatch_sharded(shard_fn, q, k, v, bias, mesh, seq_axis,
                             batch_axis)


def make_attention_fn(mesh: Mesh,
                      seq_axis: str,
                      strategy: str = "ring",
                      batch_axis: Optional[str] = None,
                      causal: bool = False,
                      use_flash: Optional[bool] = None):
    """An ``attention_fn(q, k, v, bias) -> out`` closure for models/bert.py's
    pluggable attention, bound to a mesh and strategy ("ring" | "ulysses").
    ``use_flash`` (ring only) routes each hop through the Pallas flash
    kernels — see :func:`ring_self_attention`."""
    if strategy == "ring":
        impl = functools.partial(ring_self_attention, use_flash=use_flash)
    elif strategy == "ulysses":
        impl = functools.partial(ulysses_attention, use_flash=use_flash)
    else:
        raise ValueError(f"unknown strategy {strategy!r}")

    def attention_fn(q, k, v, bias=None):
        return impl(q, k, v, mesh, seq_axis, bias=bias,
                    batch_axis=batch_axis, causal=causal)

    return attention_fn
