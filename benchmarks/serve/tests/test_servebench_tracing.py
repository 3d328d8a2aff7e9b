"""The per-layer metrics read from the program's own counters (queue
wait, host time per tick, chunk fill), in traced whole runs on the CPU
at a small size, one cell of each loop.  The small cells live in a copy
of the checkout and are added there by new files and new
``BENCHMARK.json`` entries only."""
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
CHECKOUT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from servebench.cell import _load_module, metric_file  # noqa: E402

PEAK = {"bf16_flops": 1e12}
SMALL = {
    "source": "a small dense decoder for tests", "family": "dense_decoder",
    "hidden_act": "silu", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "vocab_size": 8192,
    "serving": {"dtype": "bfloat16", "scheduler": "slot_paged",
                "prefix_cache": True, "max_len": 256, "max_batch": 4,
                "page_size": 16, "pool_pages": 64, "chunk_tokens": 16,
                "k_max": 2}}
MIXES = {
    "small-chat": {
        "loop": "open", "rate_per_s": 4.0, "clients": 4,
        "system_prompt_tokens": 16,
        "turns": {"dist": "uniform", "min": 1, "max": 2}, "turn_gap_s": 0.2,
        "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
                   "max": 16},
        "output": {"dist": "uniform", "min": 4, "max": 16}},
    "small-batch": {
        "loop": "closed", "clients": 8,
        "prompt": {"dist": "uniform", "min": 8, "max": 40},
        "output": {"dist": "uniform", "min": 4, "max": 12}},
}
# Each small cell reports what the accepted cell of its loop reports.
LIKE = {"small-chat": "smollm-chat", "small-batch": "smollm-short"}
NEW = {"small-chat": ("queue_wait_ms", "tick_host_ms.open",
                      "chunk_fill.open"),
       "small-batch": ("tick_host_ms.closed", "chunk_fill.closed")}
TICK = {"small-chat": "tick_ms.open", "small-batch": "tick_ms.closed"}
LIMIT = 0.05        # as the accepted small cell's


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmarks" / "serve",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(CHECKOUT / "src")
    b = root / "benchmarks" / "serve"
    (b / "configs" / "small.json").write_text(json.dumps(SMALL))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "small", "source": "test",
                             "file": "benchmarks/serve/configs/small.json",
                             "reduced": [], "why": "test"})
    for cell, mix in MIXES.items():
        (b / "traffic" / f"{cell}.json").write_text(json.dumps(mix))
        (b / "checks" / f"{cell}.json").write_text(
            json.dumps({"logit_gap": {"limit": LIMIT}}))
        bench["workloads"].append({"name": cell, "config": "small",
                                   "traffic": cell, "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if LIKE[cell] in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("cell", sorted(MIXES))
def test_traced_run_prints_the_program_counters(checkout, capsys, cell):
    rc = run.main(["--workload", cell, "--seed", str(2**33 + 7),
                   "--seconds", "2", "--trace", "1"],
                  checkout=checkout, require_tpu=False, peak=PEAK)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW[cell]) <= set(got), got
    assert TICK[cell] in got        # the accepted metrics still print
    for name in NEW[cell]:
        stem = name.split(".")[0]
        if stem == "queue_wait_ms":
            assert got[name] > 0
        elif stem == "chunk_fill":
            assert 0 < got[name] <= 100
        else:
            assert 0 < got[name] < got[TICK[cell]]


@pytest.mark.parametrize("name", ["queue_wait_ms", "tick_host_ms.open",
                                  "chunk_fill.closed"])
def test_reader_is_silent_on_a_program_without_the_counters(name):
    """The parent program keeps none of these counters: the reader
    returns None and does not raise."""
    reader = _load_module(metric_file(BENCH, name), "t_" + name).read
    stats = {"prefill_dispatches": 3, "prefill_tokens_saved": 0}
    ctx = SimpleNamespace(stats_open=stats, stats_end=dict(stats),
                          cell=SimpleNamespace(config=SMALL))
    assert reader(ctx) is None
