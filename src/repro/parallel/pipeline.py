"""NBB ring pipeline: pipeline parallelism as a lock-free circular buffer.

The paper's NBB (Kim'07) is a FIFO ring where a producer and a consumer
synchronize through two counters and never touch the same slot.  Mapped
onto a TPU mesh axis (DESIGN.md §2), the *stages* of a pipeline-parallel
model are the tasks, `collective_permute` edges are the MCAPI channels,
and the microbatch slots rotating around the ring are the NBB buffer:

  * producer counter  = microbatches injected at stage 0 (tick index t),
  * consumer counter  = microbatches retired at stage S-1 (t - (S-1)),
  * slot disjointness = each stage holds exactly one in-flight microbatch
    per tick, by construction — no global barrier, no lock.

Three schedules are provided, mirroring the paper's lock-based vs
lock-free test matrix:

  "barrier"  — the *lock-based analogue*: every tick all-gathers every
               stage's activation over the stage axis and each stage
               selects its input.  This is exactly the reference MCAPI
               design: one global shared-memory partition all writers
               and readers serialize through.  Collective bytes per tick
               scale with the number of stages.
  "nbb"      — the lock-free ring: one point-to-point permute per tick.
               Collective bytes per tick are one activation, independent
               of stage count — the paper's 25x insight, reproduced at
               the collective-bytes level in benchmarks/bench_pipeline.
  "nbb2"     — the 2-slot double-buffered ring (ring_depth=2): the send
               of tick t-1 has no data dependence on the compute of tick
               t, so the compiler can overlap DMA with the MXU — the
               device analogue of NBB's producer running ahead of the
               consumer.

All schedules compute identical values (property-tested); they differ
only in collective schedule — which is the paper's whole point.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _stage_slice(stage_params, n_stages):
    """shard_map hands each device its [1, ...]-leading slice; drop it."""
    return jax.tree.map(lambda a: a[0], stage_params)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,
                   microbatches: jax.Array,
                   mesh,
                   axis: str = "model",
                   schedule: str = "nbb") -> jax.Array:
    """Run ``microbatches`` through ``n_stages`` pipeline stages.

    stage_fn(params_for_stage, x[mb, ...]) -> y[mb, ...] (same shape).
    stage_params: pytree with leading dim == mesh.shape[axis] (one slice
      per stage).
    microbatches: [n_micro, mb, ...].
    Returns [n_stages, n_micro, mb, ...], sharded over ``axis`` on dim 0;
    ``result[-1]`` (index it *outside* jit to keep the transfer local) is
    the final-stage output.  Keeping delivery out of the step function
    means the compiled program contains only the schedule's own
    collectives — measurable and minimal.
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    assert schedule in ("barrier", "nbb", "nbb2")

    def run(local_params, mb_local):
        params = _stage_slice(local_params, n_stages)
        sid = jax.lax.axis_index(axis)
        first = sid == 0
        last = sid == n_stages - 1
        zero = jnp.zeros(mb_local.shape[1:], mb_local.dtype)
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        if schedule in ("barrier", "nbb"):
            n_ticks = n_micro + n_stages - 1

            def tick(buf, t):
                # stage 0 consumes the next microbatch; others their buffer
                inj = jax.lax.cond(
                    t < n_micro,
                    lambda: jax.lax.dynamic_index_in_dim(
                        mb_local, jnp.minimum(t, n_micro - 1), 0,
                        keepdims=False),
                    lambda: zero)
                x = jnp.where(first, inj, buf)
                y = stage_fn(params, x)
                if schedule == "nbb":
                    nxt = jax.lax.ppermute(y, axis, fwd)
                else:
                    # lock-based analogue: global exchange, local select
                    all_y = jax.lax.all_gather(y, axis)      # [S, mb, ...]
                    nxt = jax.lax.dynamic_index_in_dim(
                        all_y, jnp.maximum(sid - 1, 0), 0, keepdims=False)
                    nxt = jnp.where(first, zero, nxt)
                return nxt, jnp.where(last, y, zero)

            _, outs = jax.lax.scan(tick, zero, jnp.arange(n_ticks))
            outs = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, n_micro, 0)

        else:  # nbb2: 2-slot ring, send decoupled from compute
            # Each hop takes 2 ticks (slot fill, slot drain) but the
            # permute of slot w-1 is independent of the compute filling
            # slot w -> overlap.  Stage s sees microbatch m at tick
            # 2*s + m; total ticks = 2*(S-1) + n_micro.
            n_ticks = 2 * (n_stages - 1) + n_micro

            def tick(carry, t):
                held, to_send = carry          # two NBB slots
                sent = jax.lax.ppermute(to_send, axis, fwd)   # drain slot
                inj = jax.lax.cond(
                    t < n_micro,
                    lambda: jax.lax.dynamic_index_in_dim(
                        mb_local, jnp.minimum(t, n_micro - 1), 0,
                        keepdims=False),
                    lambda: zero)
                x = jnp.where(first, inj, held)
                y = stage_fn(params, x)                        # fill slot
                return (sent, y), jnp.where(last, y, zero)

            _, outs = jax.lax.scan(tick, (zero, zero), jnp.arange(n_ticks))
            # stage S-1 computes microbatch m at tick 2*(S-1) + m
            outs = jax.lax.dynamic_slice_in_dim(
                outs, 2 * (n_stages - 1), n_micro, 0)

        # Each stage returns its own outs slab; stacking over the stage
        # axis (out_specs P(axis)) delivers without any extra collective —
        # the consumer indexes the last stage's slab.  (An earlier psum
        # delivery added an all-reduce that dwarfed the schedules' own
        # traffic and hid the barrier-vs-ring difference.)
        return outs[None]

    shard_f = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(axis), P()),       # params split by stage; mbs replicated
        out_specs=P(axis),             # [n_stages, n_micro, mb, ...]
        check_vma=False,
    )
    return shard_f(stage_params, microbatches)


def pipeline_reference(stage_fn, stage_params, microbatches, n_stages):
    """Oracle: sequential stage application, no mesh."""
    def apply_all(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x

    return jax.vmap(apply_all)(microbatches)
