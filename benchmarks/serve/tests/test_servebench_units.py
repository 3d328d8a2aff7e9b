"""The benchmark's arithmetic, traffic and trace reduction, on the CPU."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
CHECKOUT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from servebench import flops, stats, trace, traffic  # noqa: E402
from servebench.cell import _load_module, load_cell, peak_for  # noqa: E402

FIXTURE = HERE / "fixtures" / "cpu_trace.xplane.pb"


def bench():
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


# -- stats ------------------------------------------------------------------
def test_percentile_is_over_all_samples():
    # Two chunks: the median of chunk medians would be 5.5; the median
    # of all samples is 2.
    a, b = [1, 2, 3], [10]
    assert stats.percentile(a + b, 50) == pytest.approx(2.5)
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([], 50) is None


def test_ttft_counts_from_due_not_from_send():
    due, sent, first = [0.0, 1.0], [0.5, 1.0], [0.7, 1.1]
    assert stats.ttft_samples(due, first) == pytest.approx([0.7, 0.1])
    assert stats.ttft_samples(sent, first) != stats.ttft_samples(due, first)


def test_gaps_and_rate():
    assert stats.gaps([0.0, 0.0, 0.25, 1.0]) == pytest.approx([0, 0.25, 0.75])
    # All events in [t0, t1) over all of the time.
    assert stats.rate([0.1, 0.2, 0.9, 1.0, 5.0], 0.0, 1.0) == 3.0


# -- operation counts -------------------------------------------------------
SMOLLM = {"d": 576, "nh": 9, "nkv": 3, "hd": 64, "f": 1536, "L": 30,
          "V": 49152}


def test_flops_hand_count_smollm():
    # Per token and layer: q 576*576, k and v 576*192 each, o 576*576,
    # MLP 3 * 576 * 1536, all times 2; attention 4 * 576 * context.
    proj = 2 * (576 * 576 + 2 * 576 * 192 + 576 * 576)
    mlp = 2 * 3 * 576 * 1536
    assert proj == 1769472 and mlp == 5308416
    assert flops.per_layer_token(SMOLLM, 10) == proj + mlp + 4 * 576 * 10
    # A 3-token prompt: contexts 1, 2, 3, plus one row of logits.
    pre = 30 * (3 * (proj + mlp) + 4 * 576 * 6) + 2 * 576 * 49152
    assert flops.prefill(SMOLLM, 3) == pre
    # Then two more tokens: fed at positions 3 and 4 (contexts 4, 5).
    dec = [30 * (proj + mlp + 4 * 576 * c) + 2 * 576 * 49152 for c in (4, 5)]
    assert flops.request(SMOLLM, 3, 3) == pre + sum(dec)
    # Roughly 2 x parameters per token for short contexts.
    per_tok = flops.decode_token(SMOLLM, 1) / 2
    assert 130e6 < per_tok < 140e6


def test_family_dims_match_the_hand_count():
    fam = _load_module(BENCH / "references" / "dense_decoder.py", "fam")
    conf = json.loads((BENCH / "configs" / "smollm-135m.json").read_text())
    assert fam.dims(conf) == SMOLLM


# -- traffic ----------------------------------------------------------------
CELLS = [w["name"] for w in bench()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_schedule(cell):
    c = load_cell(bench(), cell, root=BENCH)
    vocab = c.config["vocab_size"]
    a = traffic.make_schedule(c.mix, 2**33 + 7, vocab, 20)
    b = traffic.make_schedule(c.mix, 2**33 + 7, vocab, 20)
    assert a.sessions == b.sessions
    np.testing.assert_array_equal(a.system, b.system)
    np.testing.assert_array_equal(a.user_tokens(3, 0, 40),
                                  b.user_tokens(3, 0, 40))
    other = traffic.make_schedule(c.mix, 7, vocab, 20)
    assert (other.sessions != a.sessions) == (c.mix["loop"] == "open")
    assert not np.array_equal(other.user_tokens(3, 0, 40),
                              a.user_tokens(3, 0, 40))
    # Another seed: the same sizes and gaps (in an open loop, in another
    # order).
    for key in ("user_len", "answer_len"):
        sizes = lambda s: sorted(getattr(t, key)
                                 for x in s.sessions for t in x.turns)
        assert sizes(other) == sizes(a)
    turns = lambda s: sorted(len(x.turns) for x in s.sessions)
    assert turns(other) == turns(a)
    # Arrival gaps come from one deck; each run leaves out the gap after
    # its last arrival, so the two differ in at most that one.
    gaps = lambda s: set(np.round(np.diff([x.arrival_s for x in s.sessions]),
                                  9))
    assert len(gaps(other) ^ gaps(a)) <= 2


@pytest.mark.parametrize("cell", CELLS)
def test_every_request_fits(cell):
    c = load_cell(bench(), cell, root=BENCH)
    s = c.config["serving"]
    sched = traffic.make_schedule(c.mix, 123, c.config["vocab_size"], 51)
    for x in sched.sessions:
        for t, turn in enumerate(x.turns):
            n = traffic.prompt_len(sched, x, t)
            assert traffic.bucket(n) + turn.answer_len <= s["max_len"]
    # The whole batch of the largest requests fits the page pool too.
    traffic.check_fits(sched, s["max_len"], s["max_batch"],
                       s["pool_pages"] * s["page_size"])


def test_lengths_follow_the_mix():
    c = load_cell(bench(), "smollm-chat", root=BENCH)
    sched = traffic.make_schedule(c.mix, 5, 1000, 20)
    users = [t.user_len for x in sched.sessions for t in x.turns]
    outs = [t.answer_len for x in sched.sessions for t in x.turns]
    assert min(users) >= 16 and max(users) <= 128
    assert min(outs) >= 16 and max(outs) <= 192
    assert len(sched.sessions) == round(c.mix["rate_per_s"] * 20)
    assert {len(x.turns) for x in sched.sessions} == {1, 2, 3}


def test_oversize_traffic_is_refused():
    mix = {"loop": "closed", "clients": 2,
           "prompt": {"dist": "uniform", "min": 600, "max": 600},
           "output": {"dist": "uniform", "min": 500, "max": 500}}
    sched = traffic.make_schedule(mix, 1, 100, 1)
    with pytest.raises(ValueError):
        traffic.check_fits(sched, 1024, 1)      # bucket 1024 + 500
    with pytest.raises(ValueError):
        traffic.check_fits(sched, 2048, 2, pool_positions=2048)


# -- peaks ------------------------------------------------------------------
def test_peak_table_knows_v5e_and_refuses_others():
    p = peak_for("TPU v5 lite", BENCH)
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak_for("TPU v4", BENCH)
    with pytest.raises(KeyError):
        peak_for("cpu", BENCH)


# -- trace reduction --------------------------------------------------------
def test_union_and_complement():
    busy = trace.union([(0, 2), (1, 3), (5, 6)])
    assert busy == [(0, 3), (5, 6)]
    assert trace.complement(busy, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert trace.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]


def test_reduce_cpu_fixture():
    ev = trace.read_xplane(str(FIXTURE), "cpu")
    r = trace.reduce(ev)
    ops = [(a, b) for d in ev["devices"].values() for _, a, b in d["ops"]]
    win = [s for s in ev["spans"] if s[0] == "bench.window"][0]
    busy = sum(b - a for a, b in trace.union(ops)) * 1e-9
    assert r["window_s"] == pytest.approx((win[2] - win[1]) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy)
    assert r["idle_share"] == pytest.approx(1 - busy / r["window_s"])
    assert 0 < r["busy_s"] < r["window_s"]
    # Six program executions, the step time being their mean.
    assert r["step_count"] == 6
    assert 0 < r["step_device_s"] < r["busy_s"] * 2
    labels = [lab for lab, _ in r["breakdown"]["idle_gaps"]]
    assert labels[:2] == ["submit", "pump"]
    assert labels[2:5] == ["engine_outside_tick"] * 3
    secs = [s for _, s in r["breakdown"]["idle_gaps"]]
    assert secs == sorted(secs, reverse=True) and secs[0] >= 0.030
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert any(n.startswith("dot") for n in names)
    assert len(names) <= 10


def test_reduce_without_device_work_is_none():
    assert trace.reduce({"devices": {}, "spans": []}) is None


def test_self_times_take_nested_ops_out_of_their_loop():
    ev = [("loop", 0, 10), ("a", 1, 3), ("b", 2, 3), ("c", 4, 5),
          ("d", 12, 13)]
    got = dict(trace.self_times(ev))
    assert got == {"loop": 7, "a": 1, "b": 1, "c": 1, "d": 1}


def test_op_names_are_short():
    text = ("%fusion.375 = (f32[32,3,3,256]{3,2,1,0:T(4,128)S(1)}, f32[1]) "
            "fusion(f32[32,3,3,256,2048]{3,4,2,1,0:T(8,128)} %x), kind=kOutput")
    assert trace.op_name(text) == "fusion.375 (f32[32,3,3,256]"
    assert trace.op_name("dot_general.2") == "dot_general.2"
