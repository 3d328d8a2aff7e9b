"""One run of one cell of the serving benchmark, on the chip.

    python3 benchmarks/serve/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json`` and the
program under ``src/``.  The run makes its weights and its traffic from
``--seed``, builds the engine and compiles every program it can
dispatch (set-up), then drives it for ``--seconds`` through the session
API from one load-generator thread.  Afterwards it waits for what was
sent, checks the served tokens against the plain reference, and prints
as its last line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and, with ``--trace 1``, the
per-layer metrics in place of the end-to-end ones, and ``breakdown``),
with the numbers compared, beside their limits, under ``checks``.

It runs only on a TPU: with no TPU, with fewer chips than the cell
asks for, or on a chip missing from ``peaks.json``, it exits 2 and
prints no result.  JAX's persistent compilation cache is where the
program places it (``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``), so only a checkout's first run compiles.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
BENCH_DIR = HERE.relative_to(CHECKOUT)
# A traced run records the first seconds of its window only: a whole
# window of op events would take longer to read than the run may last.
TRACE_SECONDS = 10.0


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str, code: int) -> int:
    print(f"servebench: {msg}", file=sys.stderr)
    return code


def use_compile_cache() -> str:
    """The program's persistent compilation cache, where it places it
    (``$JAX_COMPILATION_CACHE_DIR`` when set, else a fixed directory in
    the checkout), keeping every program however fast it compiled.
    Returns the directory."""
    import jax
    from repro.launch.compile_cache import place_compile_cache

    where = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class GcPauses:
    """Collector pauses while ``active``: a pause stops every thread,
    the load generator's too."""

    def __init__(self):
        self.active, self.count, self.max_s = False, 0, 0.0
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt, self._t0 = time.perf_counter() - self._t0, None
            if self.active:
                self.count += 1
                self.max_s = max(self.max_s, dt)

    def close(self):
        gc.callbacks.remove(self._on)


class CompileCounter:
    """Counts backend compilations while ``active``: there should be
    none inside the measured window."""

    def __init__(self):
        import jax.monitoring as mon
        self.active, self.count = False, 0
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.active and name.endswith("backend_compile_duration"):
            self.count += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on)


def window_recs(out, closed: bool) -> List:
    """The requests a run answers for: in an open loop those due in the
    window, in a closed loop every request sent."""
    if closed:
        return list(out.recs)
    return [r for r in out.recs if out.t_open <= r.due < out.t_close]


def end_to_end(names: List[str], recs: List, out, setup_s: float,
               closed: bool) -> Dict[str, float]:
    from servebench import stats

    vals: Dict[str, Optional[float]] = {"setup_s": setup_s}
    done = [r for r in recs if r.first is not None]
    ttft = stats.ttft_samples([r.due for r in done], [r.first for r in done])
    itl = [g for r in recs for g in stats.gaps(r.out_stamps())]
    p50, p90 = stats.percentile(ttft, 50), stats.percentile(ttft, 90)
    vals["ttft_p50_ms"] = None if p50 is None else p50 * 1e3
    vals["ttft_p90_ms"] = None if p90 is None else p90 * 1e3
    # The 99th, not the 95th: gaps are tick lengths, which come in a few
    # fixed sizes, and the 95th percentile sat on the edge between two.
    ip99 = stats.percentile(itl, 99)
    vals["itl_p99_ms"] = None if ip99 is None else ip99 * 1e3
    vals["out_tok_s"] = stats.rate(
        (t for r in out.recs for t in r.stamps.values()),
        out.t_open, out.t_close)
    return {n: vals[n] for n in names}


def window_flops(dims: Dict[str, int], recs: List, t0: float,
                 t1: float) -> int:
    """Operations of the work whose result reached the client in the
    window: the prefill of each request whose first token came in it,
    and each later token that came in it."""
    from servebench import flops

    total = 0
    for r in recs:
        p = len(r.prompt)
        for pos, t in r.stamps.items():
            if not t0 <= t < t1:
                continue
            total += (flops.prefill(dims, p) if pos == 0
                      else flops.decode_token(dims, p + pos))
    return total


def main(argv=None, checkout: Path = CHECKOUT, require_tpu: bool = True,
         peak: Optional[Dict] = None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    root = checkout / BENCH_DIR
    src = checkout / "src"
    if not (src / "repro").is_dir():
        return fail(f"no program under {src}", 1)
    for p in (str(src), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    cache_dir = use_compile_cache()
    from servebench import check, traffic
    from servebench.cell import load_cell, peak_for
    from servebench.loadgen import LoadGen
    from servebench.serving import TickClock, build

    with open(checkout / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = load_cell(bench, args.workload, root=root)
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell.chips):
        return fail(f"needs {cell.chips} TPU chip(s), found {len(devs)} "
                    f"{dev.platform} device(s)", 2)
    try:
        peak = peak or peak_for(dev.device_kind, root)
    except KeyError as exc:
        return fail(str(exc), 2)

    conf, mix = cell.config, cell.mix
    s = conf["serving"]
    closed = mix["loop"] == "closed"
    sched = traffic.make_schedule(mix, args.seed, conf["vocab_size"],
                                  args.seconds)
    traffic.check_fits(sched, s["max_len"], s["max_batch"],
                       s["pool_pages"] * s["page_size"])
    eng, params, timing = build(cell, args.seed, sched.clients)
    tdir = None
    annotate = on_span_end = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="servebench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        annotate = jax.profiler.TraceAnnotation
    ticks = TickClock(eng, annotate)
    compiles = CompileCounter()
    # What set-up made lives through the window: leave it out of every
    # collection, so that a full one does not pause the generator.
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    thread = eng.start()
    setup_s = time.perf_counter() - t_start
    tracing = False
    stopper = threading.Thread(target=jax.profiler.stop_trace,
                               name="trace-stop")
    try:
        if args.trace:
            jax.profiler.start_trace(tdir, profiler_options=opts)
            tracing = True

            def on_span_end():
                # Writing the trace out takes seconds: off the
                # generator's thread, so that it keeps sending.
                nonlocal tracing
                stopper.start()
                tracing = False
        gen = LoadGen(eng, sched, args.seconds, s["max_batch"],
                      annotate=annotate, span_s=TRACE_SECONDS,
                      on_span_end=on_span_end)
        compiles.active = pauses.active = True
        out = gen.run()
        compiles.active = pauses.active = False
    finally:
        if tracing:
            jax.profiler.stop_trace()
        if stopper.ident is not None:
            stopper.join()
        eng.stop()
        thread.join(timeout=60)
        compiles.close()
        pauses.close()
        gc.unfreeze()
    if thread.is_alive():
        return fail("the engine thread did not stop", 1)
    mem = dev.memory_stats() or {}
    stats_end = dict(eng.stats)
    recs = window_recs(out, closed)
    failed = sum(1 for r in recs if not r.ok)
    wrong = sum(1 for r in recs
                if r.done is not None and r.error is None and not r.ok)
    missing = failed - wrong
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    result: Dict[str, object] = {"correct": False, "attempted": len(recs),
                                 "failed": failed}
    if args.trace:
        from servebench import trace as trace_mod

        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        reduced = (trace_mod.reduce(trace_mod.read_xplane(
            str(files[-1]), dev.platform)) if files else None)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = SimpleNamespace(
            cell=cell, recs=recs, all_recs=out.recs, t_open=out.t_open,
            t_close=out.t_close, seconds=out.t_close - out.t_open,
            ticks=ticks.samples, stats_open=out.stats_open,
            stats_end=stats_end, trace=reduced, peak=peak,
            window_flops=window_flops(cell.family.dims(conf), out.recs,
                                      out.t_open, out.t_close))
        metrics = {}
        for m in cell.per_layer:
            v = m.reader(ctx)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        vals = end_to_end([m.name for m in cell.end_to_end], recs, out,
                          setup_s, closed)
        metrics = {m.name: {"value": vals[m.name], "unit": m.unit}
                   for m in cell.end_to_end if vals[m.name] is not None}
    result["metrics"] = metrics
    result["device"] = device
    if args.trace and reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    late = [r.sent - r.due for r in recs if r.sent == r.sent]
    worst = max((r for r in recs if r.sent == r.sent),
                key=lambda r: r.sent - r.due, default=None)
    from servebench import stats
    itl = [g for r in recs for g in stats.gaps(r.out_stamps())]
    result["loadgen"] = {
        "itl_p95_ms": (stats.percentile(itl, 95) or 0.0) * 1e3,
        "sent": len(out.recs), "window_s": out.t_close - out.t_open,
        "late_p50_ms": (stats.percentile(late, 50) or 0.0) * 1e3,
        "late_max_ms": max(late, default=0.0) * 1e3,
        "late_max_turn": None if worst is None else worst.turn,
        "tokens_after_stream": sum(r.stream_late for r in out.recs),
        "compiles_in_window": compiles.count,
        "gc_pauses": pauses.count, "gc_max_ms": pauses.max_s * 1e3,
        "loop_gap_max_ms": out.loop_gap_max_s * 1e3,
        "compile_cache": cache_dir,
        "build_s": timing["build_s"], "warmup_s": timing["warmup_s"]}

    # The program's state goes before the reference runs, so that the
    # reference fits and does not set the peak read above.
    eng.pool.k = eng.pool.v = None
    del eng
    gc.collect()
    t_ref = time.perf_counter()
    picked = check.sample(recs, args.seed)
    g = (check.gaps(cell.family, conf, params, picked)["logit_gap"]
         if picked else None)
    checks = check.build_checks(g, cell.checks, missing, wrong)
    result["correct"] = check.judge(checks)
    result["loadgen"]["reference_s"] = time.perf_counter() - t_ref
    result["loadgen"]["compared_tokens"] = sum(r.max_tokens for r in picked)
    result["checks"] = checks
    print(json.dumps(result["loadgen"]), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
