"""Build the system under test for a cell: the program's model and
``ServeEngine`` with the benchmark's weights, warmed up."""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax


def build(cell, seed: int, n_clients: int) -> Tuple[object, Dict, Dict]:
    """``(engine, params, timings)``.  The weights come from the
    configuration's reference family, made from ``seed`` on the device
    in one call; the engine gets every serving setting from the
    configuration file."""
    from repro.models.config import ModelConfig
    from repro.models.model import build_model
    from repro.serve.engine import ServeEngine

    conf, fam = cell.config, cell.family
    t0 = time.perf_counter()
    cfg = ModelConfig(name=cell.config_name, **fam.program_config(conf))
    model = build_model(cfg)
    params = fam.make_params(conf, seed)
    _check_layout(params, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    s = conf["serving"]
    eng = ServeEngine(model, params, max_batch=s["max_batch"],
                      max_len=s["max_len"], n_clients=n_clients,
                      pool_pages=s["pool_pages"], page_size=s["page_size"],
                      scheduler=s["scheduler"], k_max=s["k_max"],
                      chunk_tokens=s["chunk_tokens"],
                      prefix_cache=s["prefix_cache"])
    jax.block_until_ready((params, eng.pool.k, eng.pool.v))
    t1 = time.perf_counter()
    programs = eng.warmup()
    t2 = time.perf_counter()
    return eng, params, {"build_s": t1 - t0, "warmup_s": t2 - t1,
                         "programs": programs}


def _check_layout(params, want) -> None:
    """The weights must have the program's parameter tree, leaf for
    leaf, or the engine would fail obscurely (or silently broadcast)."""
    got_l, got_t = jax.tree_util.tree_flatten_with_path(params)
    want_l, want_t = jax.tree_util.tree_flatten_with_path(want)
    if got_t != want_t:
        raise ValueError(f"weights tree {got_t} is not the program's "
                         f"{want_t}")
    for (path, a), (_, b) in zip(got_l, want_l):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: "
                             f"{a.shape} {a.dtype} != {b.shape} {b.dtype}")


class TickClock:
    """Wraps the engine's ``tick`` (the engine loop calls it through the
    instance) to time every call on the host clock, and to mark it as a
    ``bench.tick`` span when a trace is recorded."""

    def __init__(self, eng, annotate=None):
        self.samples: List[Tuple[float, float, bool]] = []
        self._tick = eng.tick
        self._annotate = annotate
        eng.tick = self

    def __call__(self):
        t = time.perf_counter()
        if self._annotate is None:
            served, worked = self._tick()
        else:
            with self._annotate("bench.tick"):
                served, worked = self._tick()
        self.samples.append((t, time.perf_counter() - t, bool(worked)))
        return served, worked
