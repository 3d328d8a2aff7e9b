"""Pallas flash attention (TPU target, interpret=True validation on CPU).

Online-softmax blocked attention.  The kv-sequence loop is the innermost
grid dimension, so the Pallas pipeline keeps exactly two kv tiles in
flight in VMEM — the same two-slot NBB discipline as the paper's ring
buffer (DESIGN.md §2): the DMA engine (producer) fills slot ``w mod 2``
while the MXU (consumer) reads slot ``r mod 2``; the grid guarantees the
indices never collide, which is lock-freedom by construction.

Layout: q [B, H, T, hd], k/v [B, Hkv, S, hd] (head-major so each grid
step addresses one head's contiguous tiles).  GQA is expressed through
the k/v index_map (integer division of the head index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 causal: bool, window: int, softcap: float,
                 block_q: int, block_k: int, seq_k: int, q_offset: int,
                 scale: float):
    """Grid = (B*H, T//block_q, S//block_k); kv index innermost."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # Tile-level skip: with causal masking, tiles strictly above the
    # diagonal contribute nothing; with a sliding window, tiles entirely
    # left of the window do not either.
    q_first = qi * block_q + q_offset
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1
    run = True
    if causal:
        run = jnp.logical_and(run, k_first <= q_last)
    if window:
        run = jnp.logical_and(run, k_last > q_first - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                     # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                     # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows (exp(NEG_INF - NEG_INF) = 1 garbage).
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                     # [bk, hd]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                      # masked rows -> 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: [B, T, H, hd]; k/v: [B, S, Hkv, hd] -> [B, T, H, hd].

    Causal convention matches ref.flash_attention_ref: q rows occupy the
    last T positions of the S-long kv sequence.
    """
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0 and S % block_k == 0
    block_q = min(block_q, T)
    assert T % block_q == 0
    group = H // Hkv

    # head-major layout for contiguous per-head tiles
    qm = q.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    km = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)
    vm = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)

    grid = (B * H, T // block_q, S // block_k)

    def q_map(h, qi, ki):
        return (h, qi, 0)

    def kv_map(h, qi, ki):
        return (h // group, ki, 0)

    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, seq_k=S, q_offset=S - T,
        scale=hd ** -0.5)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qm, km, vm)
    return out.reshape(B, H, T, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Differentiable wrapper: kernel forward, flash-recompute backward.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_trainable(q, k, v, causal=True, window=0, softcap=0.0,
                              block_q=128, block_k=128, interpret=False):
    """flash_attention with a VJP.  The backward pass recomputes attention
    from the residuals (q, k, v) — the standard flash-attention recompute
    strategy — expressed in jnp so XLA fuses it; the forward stays on the
    Pallas kernel."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, block_q=block_q,
                           block_k=block_k, interpret=interpret)


def _fa_fwd(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, softcap, block_q, block_k, interpret, res, g):
    from repro.kernels import ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap),
        q, k, v)
    return vjp(g)


flash_attention_trainable.defvjp(_fa_fwd, _fa_bwd)
