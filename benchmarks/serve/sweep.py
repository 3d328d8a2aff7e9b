"""Find a cell's knee: the open loop at several fixed rates, one engine.

    python3 benchmarks/serve/sweep.py --workload <open cell> \
        --rates 2,4,8 --seconds 20 --seed <n>

Builds the cell's engine once, then runs its traffic at each rate in
turn (sessions per second, the mix's other parameters unchanged) and
prints one JSON line per rate: the latency metrics, the completed rate
against the offered one, and TTFT over the window's first and last
thirds.  Below the knee the two thirds agree and every request due is
done soon after the window closes; above it the backlog grows, and the
last third's TTFT runs away.  Runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402  (the benchmark's own entry point)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.CHECKOUT / "src"))
    import jax

    run.use_compile_cache()
    from servebench import stats, traffic
    from servebench.cell import load_cell
    from servebench.loadgen import LoadGen
    from servebench.serving import TickClock, build

    bench = json.loads((run.CHECKOUT / "BENCHMARK.json").read_text())
    cell = load_cell(bench, args.workload, root=HERE)
    if jax.devices()[0].platform != "tpu":
        return run.fail("needs a TPU", 2)
    conf, s = cell.config, cell.config["serving"]
    eng, _, timing = build(cell, args.seed, cell.mix["clients"])
    ticks = TickClock(eng)
    thread = eng.start()
    print(json.dumps({"setup": timing}), flush=True)
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = dict(cell.mix, rate_per_s=rate)
            sched = traffic.make_schedule(mix, args.seed, conf["vocab_size"],
                                          args.seconds)
            t0 = time.perf_counter()
            out = LoadGen(eng, sched, args.seconds, s["max_batch"]).run()
            recs = run.window_recs(out, closed=False)
            vals = run.end_to_end(["ttft_p50_ms", "ttft_p90_ms",
                                   "itl_p99_ms"], recs, out, 0.0, False)
            third = args.seconds / 3
            early = [r.first - r.due for r in recs if r.first is not None
                     and r.due < out.t_open + third]
            late = [r.first - r.due for r in recs if r.first is not None
                    and r.due >= out.t_close - third]
            done = [r.done for r in recs if r.ok]
            wt = [dt for t, dt, w in ticks.samples
                  if w and out.t_open <= t < out.t_close]
            print(json.dumps({
                "rate": rate, **vals,
                "due": len(recs), "ok": len(done),
                "drain_s": max(done, default=out.t_close) - out.t_close,
                "ttft_p50_first_third_ms": (stats.percentile(early, 50) or 0) * 1e3,
                "ttft_p50_last_third_ms": (stats.percentile(late, 50) or 0) * 1e3,
                "tick_ms": sum(wt) / max(len(wt), 1) * 1e3,
                "wall_s": time.perf_counter() - t0}), flush=True)
    finally:
        eng.stop()
        thread.join(timeout=60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
