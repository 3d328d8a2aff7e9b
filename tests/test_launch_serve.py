"""The serving launcher's entry-point contract: how it builds the engine,
that a run whose requests fail exits non-zero, where the compile cache
goes, and that ``ServeEngine.warmup`` compiles what serving dispatches."""
import functools

import jax
import numpy as np
import pytest

from repro.core import faults as faults_mod
from repro.launch import compile_cache
from repro.launch import serve as launcher
from repro.serve.engine import ServeEngine

SMOKE = ["--arch", "smollm-135m", "--smoke", "--clients", "2",
         "--requests-per-client", "2", "--prompt-len", "8",
         "--max-tokens", "4", "--scheduler", "slot_paged"]


@pytest.fixture
def no_cache(monkeypatch):
    """Tests never turn the persistent compilation cache on."""
    monkeypatch.setattr(launcher, "place_compile_cache", lambda: None)


def test_build_engine_follows_the_options():
    args = launcher.build_parser().parse_args(SMOKE + ["--max-len", "128"])
    eng, overload, snapshot_dir = launcher.build_engine(args, seed=3)
    assert (overload, snapshot_dir) == (None, None)
    assert eng.scheduler == "slot_paged" and eng.max_batch == 8
    assert eng.pool.n_pages == 8 * 128 // 16        # the launcher's pool rule
    assert eng.prefix_cache is not None
    want = eng.model.init(jax.random.PRNGKey(3))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(eng.params), jax.tree.leaves(want)))


def test_main_serves_and_returns(no_cache, capsys):
    eng = launcher.main(SMOKE)
    assert eng.stats["served"] == 4 and launcher.serve_failure(eng) is None
    assert "served 4 requests" in capsys.readouterr().out


def test_main_exits_nonzero_when_requests_fail(no_cache, monkeypatch,
                                               capsys):
    # Every sync fails and is not retryable: the watchdog ends each bound
    # request with a FailedStatus and keeps serving.
    plan = faults_mod.FaultPlan(
        [faults_mod.FaultRule("engine.sync", times=10**6)])
    monkeypatch.setattr(launcher, "ServeEngine",
                        functools.partial(ServeEngine, fault_plan=plan))
    with pytest.raises(SystemExit) as exc:
        launcher.main(SMOKE)
    assert exc.value.code == 1
    err = capsys.readouterr().err
    assert "serve failed: 4 requests failed" in err
    assert "injected fault at engine.sync" in err


def test_serve_failure_reports_a_dead_engine():
    args = launcher.build_parser().parse_args(SMOKE)
    eng, _, _ = launcher.build_engine(args)
    assert launcher.serve_failure(eng) is None
    eng._die("boom")
    assert launcher.serve_failure(eng) == "engine died: boom"


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert calls == []                  # JAX reads the variable itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(compile_cache.CHECKOUT / ".jax_cache")
    assert compile_cache.place_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    assert (compile_cache.CHECKOUT / "chip_smoke.py").is_file()
    ignored = (compile_cache.CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("scheduler", ["slot_paged", "slot_chunked"])
def test_warmup_compiles_every_dispatch_and_changes_nothing(scheduler):
    args = launcher.build_parser().parse_args(
        SMOKE + ["--scheduler", scheduler, "--max-len", "128",
                 "--k-max", "3", "--chunk-tokens", "8"])
    outs = []
    for warm in (True, False):
        eng, _, _ = launcher.build_engine(args)
        if warm:
            assert eng.warmup() == 2 * 3 + 1
            traces = {**{("chunk", k): f._cache_size()
                         for k, f in eng._jit_chunked.items()},
                      **{("loop", k): f._cache_size()
                         for k, f in eng._jit_loops.items()}}
            assert traces == {**{("chunk", k): 1 for k in range(4)},
                              **{("loop", k): 1 for k in range(1, 4)}}
        thread = eng.start()
        session = eng.connect(0)
        rng = np.random.default_rng(0)
        got = []
        for n in (5, 20, 37):
            h = session.submit_i(rng.integers(0, 256, n), max_tokens=7)
            got.append([t for _, t in h.tokens(timeout_s=120)])
        eng.stop()
        thread.join(timeout=30)
        assert not thread.is_alive()
        if warm:                        # serving compiled nothing new
            assert all(f._cache_size() == 1 for f in
                       [*eng._jit_chunked.values(),
                        *eng._jit_loops.values()])
        assert eng.stats["tick_faults"] == 0
        outs.append(got)
    assert outs[0] == outs[1]
    assert all(len(t) == 7 for t in outs[0])


def test_warmup_refuses_other_schedulers():
    args = launcher.build_parser().parse_args(
        SMOKE + ["--scheduler", "slot_fused"])
    eng, _, _ = launcher.build_engine(args)
    with pytest.raises(ValueError, match="chunked tick"):
        eng.warmup()
