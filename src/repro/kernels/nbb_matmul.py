"""NBB double-buffered matmul — the paper's ring buffer on a TPU core.

This kernel is the most literal TPU translation of the paper's NBB
(non-blocking buffer, Kim'07): a 2-slot VMEM ring per operand where the
DMA engine is the *producer* and the MXU is the *consumer*.  The two NBB
atomic counters (update / acknowledge) become the ring indices
``k+1 mod 2`` (slot being filled) and ``k mod 2`` (slot being consumed);
DMA-completion semaphores carry the counter hand-off that x86 used atomic
increments for.  Slot disjointness is guaranteed by construction — the
producer is always exactly one step ahead — so the consumer never waits
on a lock, only on data readiness (the non-blocking property).

Operands live in HBM (``memory_space=ANY``); the kernel hand-rolls the
HBM->VMEM pipeline instead of using BlockSpec auto-pipelining, which is
the point: it demonstrates the NBB discipline explicitly.

Grid = (M//bm, N//bn); inner fori_loop over K//bk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _nbb_matmul_kernel(a_hbm, b_hbm, o_ref, a_ring, b_ring, acc_ref,
                       in_sems, *, bm, bn, bk, n_k):
    mi = pl.program_id(0)
    ni = pl.program_id(1)

    def slot_copy(kk, slot):
        """Start the DMA that fills ring slot ``slot`` with K-tile ``kk``."""
        a_dma = pltpu.make_async_copy(
            a_hbm.at[pl.ds(mi * bm, bm), pl.ds(kk * bk, bk)],
            a_ring.at[slot], in_sems.at[slot, 0])
        b_dma = pltpu.make_async_copy(
            b_hbm.at[pl.ds(kk * bk, bk), pl.ds(ni * bn, bn)],
            b_ring.at[slot], in_sems.at[slot, 1])
        a_dma.start()
        b_dma.start()
        return a_dma, b_dma

    # Prime the pipeline: producer fills slot 0 (write counter = 1).
    slot_copy(0, 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(kk, _):
        slot = jax.lax.rem(kk, 2)
        nxt = jax.lax.rem(kk + 1, 2)

        # Producer: start filling the *other* slot (non-blocking insert).
        @pl.when(kk + 1 < n_k)
        def _produce():
            slot_copy(kk + 1, nxt)

        # Consumer: wait for slot readiness (data dependency, not a lock).
        pltpu.make_async_copy(
            a_hbm.at[pl.ds(mi * bm, bm), pl.ds(kk * bk, bk)],
            a_ring.at[slot], in_sems.at[slot, 0]).wait()
        pltpu.make_async_copy(
            b_hbm.at[pl.ds(kk * bk, bk), pl.ds(ni * bn, bn)],
            b_ring.at[slot], in_sems.at[slot, 1]).wait()

        acc_ref[...] += jax.lax.dot_general(
            a_ring[slot].astype(jnp.float32),
            b_ring[slot].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return ()

    jax.lax.fori_loop(0, n_k, body, (), unroll=False)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def nbb_matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
               bk: int = 512, interpret: bool = False) -> jax.Array:
    """[M, K] @ [K, N] with an explicit 2-slot NBB VMEM ring per operand."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk

    kernel = functools.partial(_nbb_matmul_kernel, bm=bm, bn=bn, bk=bk,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # a stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # b stays in HBM
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bm, bk), a.dtype),        # NBB ring: A tiles
            pltpu.VMEM((2, bk, bn), b.dtype),        # NBB ring: B tiles
            pltpu.VMEM((bm, bn), jnp.float32),       # accumulator
            pltpu.SemaphoreType.DMA((2, 2)),         # per-slot, per-operand
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a, b)
