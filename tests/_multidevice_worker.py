"""Multi-device checks, run in a subprocess so the 8-device XLA flag never
leaks into the main pytest process (see dryrun.py note on device counts).

Exit code 0 = all checks pass.  Invoked by test_pipeline.py.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.parallel.pipeline import pipeline_apply, pipeline_reference
from repro.parallel.compression import compressed_psum


def check_pipeline_schedules():
    mesh = jax.make_mesh((8,), ("stage",), axis_types=(AxisType.Auto,))
    n_stages, n_micro, mb, d = 8, 12, 4, 16

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    k = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(k, (n_stages, d, d), jnp.float32) * 0.3,
        "b": jax.random.normal(jax.random.fold_in(k, 1),
                               (n_stages, d), jnp.float32) * 0.1,
    }
    mbs = jax.random.normal(jax.random.fold_in(k, 2),
                            (n_micro, mb, d), jnp.float32)
    want = pipeline_reference(stage_fn, params, mbs, n_stages)
    for schedule in ("barrier", "nbb", "nbb2"):
        got = pipeline_apply(stage_fn, params, mbs, mesh, axis="stage",
                             schedule=schedule)[-1]   # last stage's slab
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"schedule={schedule}")
    print("pipeline schedules OK")


def check_pipeline_collective_bytes():
    """nbb must move ~1/S the collective bytes of barrier (paper's point)."""
    import re
    mesh = jax.make_mesh((8,), ("stage",), axis_types=(AxisType.Auto,))
    n_stages, n_micro, mb, d = 8, 8, 4, 128

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    params = {"w": jnp.zeros((n_stages, d, d), jnp.float32)}
    mbs = jnp.zeros((n_micro, mb, d), jnp.float32)

    def bytes_for(schedule):
        f = jax.jit(lambda p, m: pipeline_apply(
            stage_fn, p, m, mesh, axis="stage", schedule=schedule))
        hlo = f.lower(params, mbs).compile().as_text()
        total = 0
        for line in hlo.splitlines():
            m = re.search(r"=\s+f32\[([\d,]+)\]\S*\s+(all-gather|"
                          r"collective-permute)\(", line)
            if m:
                n = 1
                for dd in m.group(1).split(","):
                    n *= int(dd)
                total += 4 * n
        return total

    b_barrier, b_nbb = bytes_for("barrier"), bytes_for("nbb")
    assert b_nbb * 4 < b_barrier, (b_nbb, b_barrier)
    print(f"collective bytes: barrier={b_barrier} nbb={b_nbb} "
          f"ratio={b_barrier / max(b_nbb, 1):.1f}x OK")


def check_compressed_psum():
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    k = jax.random.PRNGKey(3)
    # per-shard gradients [8, ...]
    g_sh = jax.random.normal(k, (8, 32, 16), jnp.float32)

    def body(g, e):
        # local leaves are [1, 32, 16] (leading shard dim); strip it
        mean, new_e = compressed_psum({"w": g[0]}, {"w": e[0]}, "data",
                                      n_shards=8)
        return mean["w"], new_e["w"][None]

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P(), P("data")), check_vma=False)
    err = jnp.zeros((8, 32, 16), jnp.float32)
    mean, err1 = f(g_sh, err)
    true_mean = g_sh.mean(0)
    q_err = np.abs(np.asarray(mean) - np.asarray(true_mean)).max()
    amax = float(jnp.abs(g_sh).max())
    assert q_err <= amax / 127.0 + 1e-6, (q_err, amax / 127.0)
    # error feedback telescopes: two steps of same grad ~ exact in sum
    mean2, err2 = f(g_sh, err1)
    two_step = (np.asarray(mean) + np.asarray(mean2))
    np.testing.assert_allclose(two_step, 2 * np.asarray(true_mean),
                               atol=2 * amax / 127.0 + 1e-5)
    print("compressed_psum OK")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.device_count()
    check_pipeline_schedules()
    check_pipeline_collective_bytes()
    check_compressed_psum()
    print("ALL MULTIDEVICE CHECKS PASSED")
