"""Whole runs of the benchmark on the CPU, at a small size.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of a run is the one the chip runs: engine, load generator, metric
readers and the comparison with the plain reference.  The small cell
lives in a copy of the checkout and is added there by new files and
new ``BENCHMARK.json`` entries only, which is how a later change adds a
configuration, a traffic mix or a per-layer metric.
"""
import filecmp
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
CHECKOUT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import calibrate  # noqa: E402
import run  # noqa: E402

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
MIX = {"loop": "open", "rate_per_s": 4.0, "clients": 4,
       "system_prompt_tokens": 16,
       "turns": {"dist": "uniform", "min": 1, "max": 2}, "turn_gap_s": 0.2,
       "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
                  "max": 16},
       "output": {"dist": "uniform", "min": 4, "max": 16}}
# Readings on the CPU, 5 seeds: the program's widest gap 0 to 0.0095,
# the float8 control's 0.19 to 0.26.
LIMIT = 0.05
READER = '''"""Tokens served to the requests due in the window."""


def read(ctx):
    return float(sum(len(r.tokens) for r in ctx.recs))
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmarks" / "serve",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(CHECKOUT / "src")
    b = root / "benchmarks" / "serve"
    # New files ...
    (b / "configs" / "small.json").write_text(json.dumps(SMALL))
    (b / "traffic" / "small-chat.json").write_text(json.dumps(MIX))
    (b / "metrics" / "served_tokens.py").write_text(READER)
    (b / "checks" / "small-chat.json").write_text(
        json.dumps({"logit_gap": {"limit": LIMIT}}))
    # ... and new entries.
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "small", "source": "test",
                             "file": "benchmarks/serve/configs/small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "small-chat", "config": "small",
                               "traffic": "small-chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smollm-chat" in m.get("workloads", []):
            m["workloads"].append("small-chat")
    bench["per_layer"].append({
        "name": "served_tokens", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "sessions and intake",
        "moves": "ttft_p50_ms", "workloads": ["small-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def cpu_run(checkout, capsys, seed, trace=0, seconds=2):
    rc = run.main(["--workload", "small-chat", "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  checkout=checkout, require_tpu=False, peak=PEAK)
    assert rc == 0
    return last_json(capsys.readouterr().out)


def test_new_files_are_picked_up(checkout, capsys):
    for sub in ("configs", "traffic", "metrics", "references", "servebench"):
        cmp = filecmp.dircmp(BENCH / sub, checkout / "benchmarks" / "serve"
                             / sub, ignore=["__pycache__"])
        assert not cmp.diff_files, sub
    res = cpu_run(checkout, capsys, seed=2**33 + 1)
    assert res["correct"] is True, res["checks"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ttft_p50_ms", "ttft_p90_ms",
                                   "itl_p99_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    res = cpu_run(checkout, capsys, seed=5, trace=1)
    assert res["correct"] is True, res["checks"]
    assert {"served_tokens", "submit_us", "prefix_hit_share",
            "tick_ms.open"} <= set(res["metrics"])
    assert res["metrics"]["served_tokens"]["value"] > 0
    assert res["device"]["window_s"] > 0
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_altered_token_is_not_correct(checkout, capsys, monkeypatch):
    import jax.numpy as jnp
    from repro.models.model import Model

    orig = Model.decode_loop

    def altered(self, *a, **k):
        block, caches = orig(self, *a, **k)
        return (jnp.where(block >= 0, (block + 1) % self.cfg.vocab_size,
                          block), caches)

    monkeypatch.setattr(Model, "decode_loop", altered)
    res = cpu_run(checkout, capsys, seed=7)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_float8_control_fails_the_limit(checkout):
    rows = calibrate.main(["--workload", "small-chat", "--seeds", "3,4",
                           "--control", "2", "--seconds", "2"],
                          checkout=checkout, require_tpu=False)
    for r in rows:
        assert r["logit_gap"] <= LIMIT < r["control_gap"], r
        assert r["correct"] is True and r["control_correct"] is False, r


def _no_result(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    p = subprocess.run([sys.executable, "benchmarks/serve/run.py",
                        "--workload", "smollm-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=300)
    return p.returncode, p.stdout


def test_without_a_tpu_there_is_no_result():
    rc, out = _no_result(CHECKOUT)
    assert rc != 0 and out == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "serve",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    rc, out = _no_result(tmp_path)
    assert rc != 0 and out == ""
