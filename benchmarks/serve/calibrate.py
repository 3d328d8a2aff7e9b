"""Readings the limit of the correctness comparison is set from.

    python3 benchmarks/serve/calibrate.py --workload <cell> \
        --seeds 1,2,3 --control 3 --seconds 10

For each seed, in one process: build the cell's engine with that seed's
weights, serve the cell's traffic for ``--seconds`` exactly as a run
does, and compare a sample of the served tokens with the float32
reference (the program's reading, ``logit_gap``).  For the first
``--control`` seeds, also put the float8 control in the program's
place on the same prompts and tokens: ``control_gap`` is the widest gap
of the tokens it would put first.  Both readings are judged against the
limit in ``checks/<cell>.json`` as a run judges its own (``correct``,
``control_correct``).  One JSON line per seed.  The limit lies above
every program reading and below every control reading.  Runs only on a
TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402  (the benchmark's own entry point)


def main(argv=None, checkout: Path = run.CHECKOUT,
         require_tpu: bool = True) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(checkout / "src"))
    import jax

    run.use_compile_cache()
    from servebench import check, traffic
    from servebench.cell import load_cell
    from servebench.loadgen import LoadGen
    from servebench.serving import build

    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    cell = load_cell(bench, args.workload, root=checkout / run.BENCH_DIR)
    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit(run.fail("needs a TPU", 2))
    conf, s = cell.config, cell.config["serving"]
    closed = cell.mix["loop"] == "closed"
    rows = []
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        sched = traffic.make_schedule(cell.mix, seed, conf["vocab_size"],
                                      args.seconds)
        eng, params, _ = build(cell, seed, sched.clients)
        thread = eng.start()
        try:
            out = LoadGen(eng, sched, args.seconds, s["max_batch"]).run()
        finally:
            eng.stop()
            thread.join(timeout=60)
        recs = run.window_recs(out, closed)
        for r in out.recs:
            r.handle = None         # a handle keeps its engine alive
        eng.pool.k = eng.pool.v = None
        del eng, out
        gc.collect()
        picked = check.sample(recs, seed)
        res = check.gaps(cell.family, conf, params, picked,
                         control=i < args.control)
        res["correct"] = check.judge(check.build_checks(
            res["logit_gap"], cell.checks, 0, 0))
        if "control_gap" in res:
            # The control in the program's place, judged as a run is.
            res["control_correct"] = check.judge(check.build_checks(
                res["control_gap"], cell.checks, 0, 0))
        res.update(seed=seed, failed=sum(1 for r in recs if not r.ok),
                   attempted=len(recs), wall_s=time.perf_counter() - t0)
        print(json.dumps(res), flush=True)
        rows.append(res)
        del params
        gc.collect()
    return rows


if __name__ == "__main__":
    main()
