"""Compile the serving step and the Pallas kernels for a described TPU v5e.

The chip is described, not attached: nothing runs, so these tests say
nothing about results or speed.  They fail where the chip's compiler
refuses a program (a block shape off the tiling, too much memory), at
no chip time.  The topology is built inside a fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.nbb_matmul import nbb_matmul
from repro.models.model import build_model
from repro.serve import kv_cache

HBM_BYTES = 16 * 2**30          # one v5e chip
# The paged serve shapes chip_smoke.py runs: 8 slots, max_len 2048,
# 16-token pages (the pool holds max_batch * max_len positions), 8-token
# chunks and 8-step fused decode blocks.
B, MAX_LEN, PAGE, CHUNK, K = 8, 2048, 16, 8, 8
# The smollm-135m benchmark's serving sizes: 32 slots, 256-token chunks.
SERVE_B, SERVE_CHUNK = 32, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it off.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HBM_BYTES, used
    return used


@pytest.mark.parametrize("step", ["chunked_block", "decode_loop"])
def test_smollm_paged_step_compiles(one_chip, step):
    cfg = get_config("smollm-135m")
    model = build_model(cfg)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = _paged_caches(one_chip, cfg, B)
    row = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    if step == "chunked_block":
        chunk = jax.ShapeDtypeStruct((B, CHUNK), jnp.int32, sharding=one_chip)
        fn = jax.jit(lambda p, c, ch, st, nv, cur, pos, rem, eos:
                     model.chunked_block(p, c, ch, st, nv, cur, pos, rem,
                                         eos, k=K, max_len=MAX_LEN),
                     donate_argnums=(1,))
        args = (params, caches, chunk, row, row, row, row, row, row)
    else:
        fn = jax.jit(lambda p, c, cur, pos, rem, eos:
                     model.decode_loop(p, c, cur, pos, rem, eos, k=K,
                                       max_len=MAX_LEN),
                     donate_argnums=(1,))
        args = (params, caches, row, row, row, row)
    compiled = fn.lower(*args).compile()
    _fits(compiled)


def _paged_caches(sharding, cfg, batch):
    """The engine's paged cache operand: the pool's arrays, shaped by
    the pool's owner, and a block table per row."""
    pages = jax.ShapeDtypeStruct(
        kv_cache.pool_shape(batch * MAX_LEN // PAGE, PAGE, cfg.num_layers,
                            cfg.num_kv_heads, cfg.head_dim_),
        cfg.compute_dtype, sharding=sharding)
    return {"pages_k": pages, "pages_v": pages,
            "block": jax.ShapeDtypeStruct((batch, MAX_LEN // PAGE),
                                          jnp.int32, sharding=sharding)}


# kv heads x head_dim: smollm-135m's 3 x 64 packs two tokens into a
# 384-wide row (g=2); 3 x 128 fills whole rows with one token (g=1).
HEAD_SHAPES = {"3x64": 64, "3x128": 128}
PROGRAMS = ["chunk", "chunk_decode_k1", "decode_k1", "decode_k8",
            "pool_copy_pages", "pool_write_pages"]


@pytest.mark.parametrize("heads", sorted(HEAD_SHAPES))
@pytest.mark.parametrize("program", PROGRAMS)
def test_no_program_relayouts_the_pool(one_chip, program, heads):
    """No engine program copies or transposes the whole KV pool, the
    pool enters every program in row-major layout (the device keeps the
    layout the program scans, so no program pays to convert it), and the
    device's own scatter writes it: a scatter the TPU cannot emit
    becomes a loop of one small update at a time, and leaves none."""
    cfg = dataclasses.replace(get_config("smollm-135m"),
                              head_dim=HEAD_SHAPES[heads])
    model = build_model(cfg)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = _paged_caches(one_chip, cfg, SERVE_B)
    pages = caches["pages_k"]
    row = jax.ShapeDtypeStruct((SERVE_B,), jnp.int32, sharding=one_chip)
    chunk = jax.ShapeDtypeStruct((SERVE_B, SERVE_CHUNK), jnp.int32,
                                 sharding=one_chip)
    two = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    k = int(program[-1]) if program[-1].isdigit() else 0
    if program == "chunk":
        fn = jax.jit(model.prefill_chunk_into, donate_argnums=(1,))
        args = (params, caches, chunk, row, row)
    elif program.startswith("chunk_decode"):
        fn = jax.jit(lambda p, c, ch, st, nv, cur, pos, rem, eos:
                     model.chunked_block(p, c, ch, st, nv, cur, pos, rem,
                                         eos, k=k, max_len=MAX_LEN),
                     donate_argnums=(1,))
        args = (params, caches, chunk, row, row, row, row, row, row)
    elif program.startswith("decode"):
        fn = jax.jit(lambda p, c, cur, pos, rem, eos:
                     model.decode_loop(p, c, cur, pos, rem, eos, k=k,
                                       max_len=MAX_LEN),
                     donate_argnums=(1,))
        args = (params, caches, row, row, row, row)
    elif program == "pool_copy_pages":
        fn = kv_cache._copy_pages_program(2)
        args = (pages, pages, two, two)
    else:
        image = jax.ShapeDtypeStruct(
            (2, pages.shape[0]) + pages.shape[2:], pages.dtype,
            sharding=one_chip)
        fn = kv_cache._write_pages_program(2)
        args = (pages, pages, two, image, image)
    compiled = fn.lower(*args).compile()
    _fits(compiled)
    text = compiled.as_text()
    dims = ",".join(map(str, pages.shape))
    pool = re.compile(r"%([\w.-]+) = \w+\[" + dims
                      + r"\]\{[^}]*\} ([\w-]+)\(")
    relayouts = [m.group(0) for m in pool.finditer(text)
                 if {"copy", "transpose"} & {m.group(1).split(".")[0],
                                             m.group(2)}]
    assert not relayouts, relayouts
    assert text.count(" scatter(") >= 2, "the pool is not written by a scatter"
    header = text[:text.index("\n")]
    layouts = re.findall(r"\[" + dims + r"\]\{([\d,]+)", header)
    assert layouts and set(layouts) == {"3,2,1,0"}, layouts


def test_flash_attention_compiles(one_chip):
    """smollm-135m's attention over a 2048-token prompt: 9 query heads,
    3 kv heads, head_dim 64."""
    q = jax.ShapeDtypeStruct((1, 2048, 9, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, 3, 64), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(flash_attention).lower(q, kv, kv).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_nbb_matmul_compiles(one_chip):
    """qwen3-14b's MLP up-projection over 2048 tokens.  smollm-135m's
    d_model of 576 is not a multiple of the 128-lane tiling, which this
    kernel's slices need."""
    a = jax.ShapeDtypeStruct((2048, 5120), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((5120, 17408), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(nbb_matmul).lower(a, b).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()
