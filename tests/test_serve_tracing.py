"""The serving tick's own spans and counters: ``serve.*`` profiler spans
nested in ``serve.tick``, step programs under stable names, and the
counters the benchmark reads (queue wait, host time per tick, chunk
fill), on a small model through ``slot_paged``."""
import glob
import re
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models.model import build_model
from repro.serve.engine import ServeEngine

jax.config.update("jax_platform_name", "cpu")

PHASES = ("serve.intake", "serve.plan", "serve.block_table",
          "serve.dispatch", "serve.sync", "serve.harvest")
# Prompts of 3..20 tokens bucket to 8, 8, 16 and 32 positions: chunks of
# 8 carry left padding alone, in part, and not at all.
LENGTHS = (3, 8, 13, 20)


@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_smoke_config("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **kw):
    return ServeEngine(model, params, max_batch=2, max_len=64, n_clients=1,
                       pool_pages=64, page_size=8, scheduler="slot_paged",
                       k_max=2, chunk_tokens=8, prefix_cache=False, **kw)


def _submit(eng, vocab, max_tokens=5):
    return [eng.submit(0, (np.arange(n) + i) % vocab, max_tokens=max_tokens)
            for i, n in enumerate(LENGTHS)]


def _responses(eng, n):
    out = [eng.get_response(0, timeout_s=10) for _ in range(n)]
    assert all(out), "response timed out"
    return out


def test_counters_add_up(engine_setup):
    cfg, model, params = engine_setup
    eng = _engine(model, params)
    eng.warmup()
    reqs = _submit(eng, cfg.vocab_size)
    wall_ns = 0
    while True:
        t = time.monotonic_ns()
        _, worked = eng.tick()
        wall_ns += time.monotonic_ns() - t
        if not worked:
            break
    done = _responses(eng, len(reqs))
    st = eng.stats
    assert st["served"] == len(LENGTHS)
    assert st["binds"] == len(LENGTHS)
    total_ns = sum(r.done_t - r.submit_t for r in done) * 1e9
    assert 0 <= st["queue_wait_ns"] <= total_ns
    # Every prompt token is dispatched once; the bucket's padding is not
    # counted.
    assert st["chunk_tokens"] == sum(LENGTHS)
    assert st["prefill_dispatches"] > 0
    assert st["ticks_worked"] > 0 and st["tick_sync_ns"] > 0
    assert 0 < st["tick_host_ns"] + st["tick_sync_ns"] <= wall_ns
    for r in done:       # one lifecycle per request
        assert r.submit_t <= r.bind_t <= r.first_token_t <= r.done_t
        assert r.token_ts[0] == r.first_token_t
        assert r.token_ts == sorted(r.token_ts)


def test_tokens_are_stamped_when_the_host_received_them(engine_setup):
    """A K-step block's tokens all carry the time its fetch returned:
    one stamp per dispatch, never points between two."""
    cfg, model, params = engine_setup
    eng = _engine(model, params)
    reqs = _submit(eng, cfg.vocab_size, max_tokens=7)
    eng.step()
    done = _responses(eng, len(reqs))
    for r in done:
        assert len(r.token_ts) == len(r.tokens_out) == 7
        # k_max=2: at least 4 dispatches for 7 tokens, and blocks of 2
        # tokens share their stamp.
        assert len(set(r.token_ts)) < len(r.token_ts)


def test_trace_nests_phases_and_names_programs(engine_setup, tmp_path):
    cfg, model, params = engine_setup
    eng = _engine(model, params)
    eng.warmup()
    reqs = _submit(eng, cfg.vocab_size)
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
    _responses(eng, len(reqs))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ticks, phases, programs = [], [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                key = (plane.name, line.name)
                if ev.name == "serve.tick":
                    ticks.append((key, ev.start_ns, ev.end_ns))
                elif ev.name.startswith("serve."):
                    phases.append((ev.name, key, ev.start_ns, ev.end_ns))
                elif ev.name.startswith("PjitFunction("):
                    programs.append(ev.name)
    assert ticks
    assert {name for name, *_ in phases} == set(PHASES)
    for name, key, a, b in phases:
        assert any(k == key and ta <= a and b <= tb
                   for k, ta, tb in ticks), f"{name} outside serve.tick"
    # The engine thread called only named step programs (a lambda
    # would read ``PjitFunction(<lambda>)``) ...
    assert programs
    assert all(re.fullmatch(r"PjitFunction\(serve_\w+\)", p)
               for p in programs), set(programs)
    # ... whose modules the trace names ``jit_serve_*``.
    raw = Path(path).read_bytes()
    assert b"jit_serve_chunk_decode_k2" in raw
    assert b"jit_serve_decode_k2" in raw


def test_copy_program_is_named(engine_setup):
    cfg, model, params = engine_setup
    eng = _engine(model, params)
    eng.pool._copy_pages([0, 1], [2, 3])
    (fn,) = eng.pool._cow_fns.values()
    text = fn.lower(eng.pool.k, eng.pool.v, np.zeros(2, np.int32),
                    np.zeros(2, np.int32)).as_text()
    assert "module @jit_pool_copy_pages_n2" in text


def test_restore_of_a_snapshot_without_the_new_counters(engine_setup,
                                                         tmp_path):
    """A snapshot written before a counter existed restores with that
    counter at 0, and the engine goes on counting."""
    cfg, model, params = engine_setup
    d = str(tmp_path / "snaps")
    eng = _engine(model, params, snapshot_dir=d)
    reqs = _submit(eng, cfg.vocab_size)
    for _ in range(2):
        eng.tick()
    snap = eng.snapshot()
    for key in ("binds", "queue_wait_ns", "ticks_worked", "tick_host_ns",
                "tick_sync_ns", "chunk_tokens"):
        del snap.stats[key]
    eng2 = _engine(model, params, snapshot_dir=d)
    eng2.restore(snap)
    assert eng2.stats["restores"] == 1
    assert eng2.stats["binds"] == 0 and eng2.stats["chunk_tokens"] == 0
    eng2.step()
    assert eng2.stats["served"] == len(reqs)
    assert eng2.stats["ticks_worked"] > 0
    # Two requests were bound before the snapshot: only the two queued
    # ones bind for the first time here.
    assert eng2.stats["binds"] == 2
