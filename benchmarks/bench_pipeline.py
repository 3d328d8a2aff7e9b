"""Device-level analogue of the paper's lock vs lock-free measurement.

The host benchmark (bench_lockfree) measures mutex vs NBB rings between
threads.  On TPU the same contrast is *barrier-style global exchange*
(all-gather the world every tick — the reference MCAPI global lock) vs
the NBB point-to-point ring (collective_permute).  We compile both
schedules for an 8-stage pipeline and compare:

  * collective bytes in the optimized HLO (the paper's "bus demand"),
  * wall time per microbatch on 8 host devices (CPU stand-in; the HLO
    byte ratio is hardware-independent and is what transfers to TPU).

Runs in a subprocess because it needs 8 forced host devices.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, re, time
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.parallel.pipeline import pipeline_apply

mesh = jax.make_mesh((8,), ("stage",), axis_types=(AxisType.Auto,))
S, M, B, D = 8, 16, 8, 256

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"])

params = {"w": jax.random.normal(jax.random.PRNGKey(0), (S, D, D),
                                 jnp.float32) * 0.1}
mbs = jax.random.normal(jax.random.PRNGKey(1), (M, B, D), jnp.float32)

out = {}
for schedule in ("barrier", "nbb", "nbb2"):
    f = jax.jit(lambda p, m, s=schedule: pipeline_apply(
        stage_fn, p, m, mesh, axis="stage", schedule=s))
    lowered = f.lower(params, mbs)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    coll = 0
    for line in hlo.splitlines():
        mm = re.search(r"=\s+f32\[([\d,]+)\]\S*\s+(all-gather|"
                       r"collective-permute|all-reduce)\(", line)
        if mm:
            n = 1
            for d in mm.group(1).split(","):
                n *= int(d)
            coll += 4 * n
    r = f(params, mbs); jax.block_until_ready(r)   # warm
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        r = f(params, mbs)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / reps
    out[schedule] = {"collective_bytes": coll,
                     "us_per_microbatch": dt / M * 1e6}
print(json.dumps(out))
"""


def run() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env.pop("XLA_FLAGS", None)
    # The child measures 8 virtual CPU devices.  Pin it to the CPU so it
    # never contends for an accelerator a parent process may hold.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _WORKER],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    out = run()
    print("schedule,collective_bytes,us_per_microbatch")
    for k, v in out.items():
        print(f"{k},{v['collective_bytes']},{v['us_per_microbatch']:.1f}")
    ratio = out["barrier"]["collective_bytes"] / max(
        out["nbb"]["collective_bytes"], 1)
    print(f"barrier_vs_nbb_bytes_ratio,{ratio:.1f}")
    return out


if __name__ == "__main__":
    main()
