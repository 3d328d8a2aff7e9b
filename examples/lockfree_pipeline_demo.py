"""The paper's experiment at device level: lock-based (barrier) vs
lock-free (NBB ring) pipeline exchange on an 8-device mesh.

    PYTHONPATH=src python examples/lockfree_pipeline_demo.py

Prints per-schedule collective bytes from the compiled HLO (hardware-
independent — this ratio is what transfers to TPU) plus CPU wall time,
and verifies all schedules compute identical results.
"""
import os

# must precede jax import: fork 8 host devices for a real mesh
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.parallel.pipeline import pipeline_apply, pipeline_reference


def main():
    mesh = jax.make_mesh((8,), ("stage",), axis_types=(AxisType.Auto,))
    S, M, B, D = 8, 16, 8, 256

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (S, D, D), jnp.float32) * 0.1}
    mbs = jax.random.normal(jax.random.fold_in(key, 1), (M, B, D),
                            jnp.float32)
    want = pipeline_reference(stage_fn, params, mbs, S)

    import re
    print(f"{'schedule':10} {'collective bytes':>18} {'ms/call':>8}  match")
    for schedule in ("barrier", "nbb", "nbb2"):
        f = jax.jit(lambda p, m, s=schedule: pipeline_apply(
            stage_fn, p, m, mesh, axis="stage", schedule=s))
        compiled = f.lower(params, mbs).compile()
        coll = 0
        for line in compiled.as_text().splitlines():
            mm = re.search(r"=\s+f32\[([\d,]+)\]\S*\s+(all-gather|"
                           r"collective-permute|all-reduce)\(", line)
            if mm:
                n = 1
                for d in mm.group(1).split(","):
                    n *= int(d)
                coll += 4 * n
        out = f(params, mbs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(10):
            out = f(params, mbs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 10
        ok = np.allclose(np.asarray(out)[-1], np.asarray(want), atol=1e-5)
        print(f"{schedule:10} {coll:18,} {dt * 1e3:8.1f}  {ok}")
    print("\nbarrier = the reference MCAPI global lock (everyone exchanges "
          "with everyone);\nnbb = the paper's lock-free ring (point-to-point"
          " only). Fewer collective\nbytes at identical results is the "
          "paper's 25x, restated for TPU meshes.")


if __name__ == "__main__":
    main()
