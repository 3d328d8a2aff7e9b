"""Memory of each configuration's serving step, compiled for a
described TPU v5e (no chip attached; nothing runs).

    JAX_PLATFORMS=cpu python3 benchmarks/serve/topology_memory.py

For every configuration file it compiles every step program the engine
can dispatch (the chunk alone, and the chunk fused with, or without, K
decode steps for K = 1 .. k_max) at the file's serving sizes, and
prints the weights, the KV pool, and each program's temporaries and
total, or the compiler's refusal.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models.config import ModelConfig
    from repro.models.model import build_model
    from servebench.cell import _load_module

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for path in sorted((HERE / "configs").glob("*.json")):
        conf = json.loads(path.read_text())
        fam = _load_module(HERE / "references" / f"{conf['family']}.py",
                           "ref")
        s = conf["serving"]
        cfg = ModelConfig(name=path.stem, **fam.program_config(conf))
        model = build_model(cfg)
        on = lambda t: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
        params = on(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        B, L, P, C, K = (s["max_batch"], s["max_len"], s["page_size"],
                         s["chunk_tokens"], s["k_max"])
        pages = jax.ShapeDtypeStruct(
            (s["pool_pages"], P, cfg.num_layers, cfg.num_kv_heads,
             cfg.head_dim_), cfg.compute_dtype, sharding=one)
        caches = {"pages_k": pages, "pages_v": pages,
                  "block": jax.ShapeDtypeStruct((B, L // P), jnp.int32,
                                                sharding=one)}
        row = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
        chunk = jax.ShapeDtypeStruct((B, C), jnp.int32, sharding=one)
        w = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
        kv = 2 * pages.size * pages.dtype.itemsize
        print(f"{path.stem}: weights {w / 1e9:.3f} GB, KV pool "
              f"{kv / 1e9:.3f} GB")
        progs = [("chunk", jax.jit(
            lambda p, c, ch, st, nv: model.prefill_chunk_into(
                p, c, ch, st, nv), donate_argnums=(1,)),
            (params, caches, chunk, row, row))]
        for k in range(1, K + 1):
            progs.append((f"chunk + {k} steps", jax.jit(
                lambda p, c, ch, st, nv, cur, pos, rem, eos, k=k:
                model.chunked_block(p, c, ch, st, nv, cur, pos, rem, eos,
                                    k=k, max_len=L), donate_argnums=(1,)),
                (params, caches, chunk, row, row, row, row, row, row)))
            progs.append((f"{k} steps", jax.jit(
                lambda p, c, cur, pos, rem, eos, k=k: model.decode_loop(
                    p, c, cur, pos, rem, eos, k=k, max_len=L),
                donate_argnums=(1,)), (params, caches, row, row, row, row)))
        for name, fn, args in progs:
            try:
                ma = fn.lower(*args).compile().memory_analysis()
            except jax.errors.JaxRuntimeError as exc:
                print(f"  {name}: refused: {str(exc).splitlines()[0]}")
                continue
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            print(f"  {name}: temporaries {ma.temp_size_in_bytes / 1e9:.3f}"
                  f" GB, total {total / 1e9:.3f} GB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
