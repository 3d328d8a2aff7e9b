"""Overload-control subsystem tests (DESIGN.md §12): priority intake,
page-swap preemption, WFQ, aging, and SLO shedding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import nbb, states
from repro.models.model import build_model
from repro.serve.engine import ServeEngine
from repro.serve.kv_cache import OK, POOL_FULL, PagedKVPool
from repro.serve.overload import (PRIORITY_HIGH, PRIORITY_LOW,
                                  PRIORITY_NORMAL, OverloadPolicy,
                                  PriorityIntake, ShedStatus)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except Exception:                                   # pragma: no cover
    st = None

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# PriorityIntake units (no model)
# ---------------------------------------------------------------------------
def test_intake_strict_priority_order():
    q = PriorityIntake(1, OverloadPolicy(wfq=False))
    for item, pri in [("l1", PRIORITY_LOW), ("n1", PRIORITY_NORMAL),
                      ("h1", PRIORITY_HIGH), ("h2", PRIORITY_HIGH)]:
        assert q.producer(0, pri).insert_item(item) == nbb.OK
    got = [q.pop()[1] for _ in range(4)]
    assert got == ["h1", "h2", "n1", "l1"]      # classes first, FIFO within
    assert q.pop() == (nbb.BUFFER_EMPTY, None, False)


def test_intake_aging_promotes_starved_class():
    q = PriorityIntake(1, OverloadPolicy(wfq=False, aging_limit=2))
    assert q.producer(0, PRIORITY_LOW).insert_item("low") == nbb.OK
    ring_h = q.producer(0, PRIORITY_HIGH)
    order = []
    for i in range(6):
        assert ring_h.insert_item(f"h{i}") == nbb.OK
    for _ in range(7):
        status, item, promoted = q.pop()
        assert status == nbb.OK
        order.append((item, promoted))
    # low is bypassed aging_limit=2 times, then served next — promoted.
    assert order[0] == ("h0", False) and order[1] == ("h1", False)
    assert order[2] == ("low", True)
    assert [it for it, _ in order[3:]] == ["h2", "h3", "h4", "h5"]


def test_intake_wfq_interleaves_flooding_client():
    q = PriorityIntake(2, OverloadPolicy())
    for i in range(6):
        assert q.producer(0, PRIORITY_NORMAL).insert_item(("a", i)) == nbb.OK
    for i in range(2):
        assert q.producer(1, PRIORITY_NORMAL).insert_item(("b", i)) == nbb.OK
    got = []
    for _ in range(8):
        status, (cid, i), _ = q.pop()
        assert status == nbb.OK
        got.append(cid)
        q.charge(0 if cid == "a" else 1, 10.0)  # equal cost per pop
    # equal weights: client b's two items are served within the first
    # four pops instead of waiting behind client a's entire burst.
    assert got[:4].count("b") == 2
    assert got.count("a") == 6 and got.count("b") == 2


def test_intake_wfq_weights_bias_service():
    q = PriorityIntake(2, OverloadPolicy(weights=(3.0, 1.0)))
    for i in range(6):
        q.producer(0, PRIORITY_NORMAL).insert_item(("a", i))
        q.producer(1, PRIORITY_NORMAL).insert_item(("b", i))
    got = []
    for _ in range(8):
        _, (cid, _), _ = q.pop()
        got.append(cid)
        q.charge(0 if cid == "a" else 1, 12.0)
    # weight 3:1 -> client a gets ~3 pops per b pop over the window.
    assert got[:8].count("a") >= 5


def test_intake_priorities_off_single_class():
    q = PriorityIntake(3, OverloadPolicy(priorities=False), 8)
    assert q.n_classes == 1
    # any priority routes to the one class; round-robin across clients.
    q.producer(0, PRIORITY_HIGH).insert_item("x")
    q.producer(1, PRIORITY_LOW).insert_item("y")
    assert {q.pop()[1], q.pop()[1]} == {"x", "y"}


def test_policy_validation():
    with pytest.raises(ValueError):
        OverloadPolicy(n_classes=0)
    with pytest.raises(ValueError):
        OverloadPolicy(aging_limit=0)


def test_shed_status_is_falsy():
    s = ShedStatus(waited_s=1.5, slo_s=1.0, priority=PRIORITY_LOW)
    assert not s and isinstance(s, ShedStatus)


# ---------------------------------------------------------------------------
# pool: page-swap preemption
# ---------------------------------------------------------------------------
def _fill_pages(pool, pages, base):
    """Stamp identifiable values into whole pages of the pool arrays."""
    shape = (len(pages),) + pool.page_shape
    kv = base + jnp.arange(np.prod(shape), dtype=pool.k.dtype).reshape(shape)
    pool.write_pages(pages, kv, kv + 0.5)
    return np.asarray(kv), np.asarray(kv + 0.5)


def test_pool_preempt_roundtrip_byte_identical():
    pool = PagedKVPool(8, page_size=4, n_layers=2, kv_heads=2, head_dim=4,
                       dtype=jnp.float32)
    assert pool.try_admit(1, 10) == OK          # 3 pages
    want_k, want_v = _fill_pages(pool, pool.table(1).pages, 100.0)
    img = pool.swap_out_preempt(1, 10)
    assert img.rows == [0, 1, 2] and not img.dead_rows and not img.shared_rows
    assert pool.table(1).pages == [-1, -1, -1]
    assert pool.free_pages() == 8               # pages really released
    assert pool.swap_out_bytes == 3 * pool.page_nbytes
    # another sequence can take (and dirty) the freed pages meanwhile
    assert pool.try_admit(2, 16) == OK
    _fill_pages(pool, pool.table(2).pages, 900.0)
    pool.free(2)
    assert pool.swap_in_preempt(1, img) == OK
    pages = pool.table(1).pages
    assert all(p >= 0 for p in pages)
    got_k, got_v = pool.read_pages(pages)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)
    assert pool.swap_in_bytes == 3 * pool.page_nbytes
    assert pool.kv_copy_bytes == pool.swap_in_bytes + pool.swap_out_bytes
    pool.free(1)
    assert pool.free_pages() == 8


def test_pool_preempt_skips_reserved_ahead_pages():
    pool = PagedKVPool(8, page_size=4, n_layers=1, kv_heads=1, head_dim=2,
                       dtype=jnp.float32)
    assert pool.try_admit(1, 20) == OK          # 5 pages reserved
    img = pool.swap_out_preempt(1, 6)           # only 2 pages live
    assert img.rows == [0, 1] and img.dead_rows == [2, 3, 4]
    # only live pages were copied; dead ones released for free
    assert pool.swap_out_bytes == 2 * pool.page_nbytes
    assert pool.free_pages() == 8
    assert pool.swap_in_preempt(1, img) == OK
    assert all(p >= 0 for p in pool.table(1).pages)
    pool.free(1)


def test_pool_preempt_never_moves_shared_pages():
    """Satellite regression: refcount>1 pages (a prefix-cache hit's
    shared prefix) stay resident through preempt/resume — never copied,
    never released, cow_copy_bytes untouched."""
    pool = PagedKVPool(8, page_size=4, n_layers=1, kv_heads=2, head_dim=4,
                       dtype=jnp.float32)
    assert pool.try_admit(1, 12) == OK          # 3 pages
    t = pool.table(1)
    shared = t.pages[0]
    pool.incref_pages([shared])                 # the cache's residency ref
    want_k = pool.read_pages([shared])[0]
    img = pool.swap_out_preempt(1, 12)
    assert img.shared_rows == [0] and img.rows == [1, 2]
    assert t.pages[0] == shared                 # row still valid, parked
    assert pool.refcount(shared) == 2           # both refs intact
    assert pool.swap_out_bytes == 2 * pool.page_nbytes
    assert pool.cow_copy_bytes == 0
    assert pool.swap_in_preempt(1, img) == OK
    assert t.pages[0] == shared                 # never moved
    np.testing.assert_array_equal(pool.read_pages([shared])[0], want_k)
    assert pool.cow_copy_bytes == 0
    pool.free(1)                                # drops the seq's ref only
    assert pool.refcount(shared) == 1
    pool.decref_pages([shared])
    assert pool.free_pages() == 8


def test_pool_swap_in_pool_full_leaves_image_intact():
    pool = PagedKVPool(4, page_size=4, n_layers=1, kv_heads=1, head_dim=2,
                       dtype=jnp.float32)
    assert pool.try_admit(1, 8) == OK           # 2 pages
    img = pool.swap_out_preempt(1, 8)
    assert pool.try_admit(2, 16) == OK          # hog the whole pool
    assert pool.swap_in_preempt(1, img) == POOL_FULL
    assert pool.table(1).pages == [-1, -1]      # untouched, retryable
    pool.free(2)
    assert pool.swap_in_preempt(1, img) == OK
    pool.free(1)
    assert pool.free_pages() == 4


def test_pool_free_while_parked():
    """A parked (tombstoned) sequence frees cleanly — no double-release
    of pages it no longer holds."""
    pool = PagedKVPool(4, page_size=4, n_layers=1, kv_heads=1, head_dim=2)
    assert pool.try_admit(1, 8) == OK
    pool.swap_out_preempt(1, 8)
    pool.free(1)
    assert pool.free_pages() == 4 and pool.n_seqs() == 0


if st is not None:
    @settings(deadline=None, max_examples=20)
    @given(st.lists(st.integers(0, 3), min_size=1, max_size=32))
    def test_pool_preempt_resume_storm(ops):
        """Randomized admit/preempt/resume/free interleavings: pages are
        never double-freed or leaked, and every resume (and survivor)
        reads back the exact bytes written at admission."""
        pool = PagedKVPool(12, page_size=2, n_layers=1, kv_heads=1,
                           head_dim=2, dtype=jnp.float32)
        nxt = 0
        live, parked, want = {}, {}, {}
        for op in ops:
            if op == 0:                             # admit + stamp
                n_tok = 3 + (nxt % 3)
                if pool.try_admit(nxt, n_tok) == OK:
                    k, _ = _fill_pages(pool, pool.table(nxt).pages,
                                       100.0 * (nxt + 1))
                    live[nxt], want[nxt] = n_tok, k
                    nxt += 1
            elif op == 1 and live:                  # preempt oldest live
                sid = min(live)
                parked[sid] = pool.swap_out_preempt(sid, live.pop(sid))
            elif op == 2 and parked:                # resume oldest parked
                sid = min(parked)
                if pool.swap_in_preempt(sid, parked[sid]) == OK:
                    img = parked.pop(sid)
                    live[sid] = img.k.shape[0] * pool.page_size
                    pages = pool.table(sid).pages
                    np.testing.assert_array_equal(
                        pool.read_pages(pages)[0], want[sid])
            elif op == 3 and (live or parked):      # free newest
                sid = max(list(live) + list(parked))
                live.pop(sid, None)
                parked.pop(sid, None)
                pool.free(sid)
        for sid in live:                            # survivors unscathed
            pages = pool.table(sid).pages
            np.testing.assert_array_equal(
                pool.read_pages(pages)[0], want[sid])
        for sid in list(live) + list(parked):
            pool.free(sid)
        assert pool.free_pages() == pool.n_pages    # nothing leaked
        assert pool.kv_copy_bytes == (pool.swap_in_bytes
                                      + pool.swap_out_bytes)
else:                                               # pragma: no cover
    def test_pool_preempt_resume_storm():
        pytest.skip("hypothesis not installed")


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_smoke_config("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _mk(model, params, overload=None, max_batch=1, pool_pages=24):
    return ServeEngine(model, params, max_batch=max_batch, max_len=64,
                       n_clients=2, pool_pages=pool_pages, page_size=8,
                       scheduler="slot_paged", k_max=4, chunk_tokens=16,
                       overload=overload)


def test_preemption_requires_paged_scheduler(engine_setup):
    _, model, params = engine_setup
    with pytest.raises(ValueError, match="slot_paged"):
        ServeEngine(model, params, scheduler="slot_chunked",
                    overload=OverloadPolicy())
    # preemption off: any scheduler takes a policy
    eng = ServeEngine(model, params, scheduler="slot_fused",
                      overload=OverloadPolicy(preemption=False))
    assert eng._ov is not None


def test_engine_preempt_resume_byte_identical(engine_setup):
    """The tentpole end-to-end: a high-priority arrival preempts the
    decoding low-priority sequence (private pages swap host-side), runs
    to completion, and the victim resumes — both token streams exactly
    equal the unpreempted runs, and every copied byte is attributed to
    swap traffic."""
    cfg, model, params = engine_setup
    low_prompt = np.arange(8) % cfg.vocab_size
    high_prompt = (np.arange(6) + 3) % cfg.vocab_size

    eng = _mk(model, params)
    h = eng.connect(0).submit_i(low_prompt, max_tokens=16)
    eng.step()
    ref_low = h.wait(timeout_s=60).tokens_out.copy()
    eng = _mk(model, params)
    h = eng.connect(1).submit_i(high_prompt, max_tokens=4)
    eng.step()
    ref_high = h.wait(timeout_s=60).tokens_out.copy()

    eng = _mk(model, params, overload=OverloadPolicy())
    hl = eng.connect(0).submit_i(low_prompt, max_tokens=16,
                                 priority=PRIORITY_LOW)
    for _ in range(3):                  # low is mid-decode ...
        eng.tick()
    hh = eng.connect(1).submit_i(high_prompt, max_tokens=4,
                                 priority=PRIORITY_HIGH)
    eng.step()                          # ... high preempts, then low resumes
    rl, rh = hl.wait(timeout_s=60), hh.wait(timeout_s=60)
    assert eng.stats["preemptions"] >= 1 and eng.stats["resumes"] >= 1
    assert rl.fsm.state == states.REQUEST_COMPLETED
    assert rh.fsm.state == states.REQUEST_COMPLETED
    np.testing.assert_array_equal(rl.tokens_out, ref_low)
    np.testing.assert_array_equal(rh.tokens_out, ref_high)
    # copied bytes are swap traffic, wholly and exactly
    pool = eng.pool
    assert pool.swap_out_bytes > 0
    assert pool.kv_copy_bytes == (pool.cow_copy_bytes + pool.swap_in_bytes
                                  + pool.swap_out_bytes)
    assert eng.stats["swap_in_bytes"] == pool.swap_in_bytes
    assert pool.free_pages() == pool.n_pages        # nothing leaked
    assert not eng._parked
    ttft = eng.class_ttft()
    assert set(ttft) == {PRIORITY_HIGH, PRIORITY_LOW}


def test_engine_preempted_slot_fsm_states(engine_setup):
    """The Figure-4 extension live: while parked the sequence's cell is
    BUFFER_PREEMPTED and the vacated slot's fresh cell binds the
    preemptor; the resume CASes PREEMPTED -> ALLOCATED."""
    cfg, model, params = engine_setup
    eng = _mk(model, params, overload=OverloadPolicy())
    hl = eng.connect(0).submit_i(np.arange(8) % cfg.vocab_size,
                                 max_tokens=16, priority=PRIORITY_LOW)
    for _ in range(3):
        eng.tick()
    eng.connect(1).submit_i((np.arange(6) + 3) % cfg.vocab_size,
                            max_tokens=8, priority=PRIORITY_HIGH)
    eng.tick()                          # sweep preempts + binds high
    assert len(eng._parked) == 1
    parked = eng._parked[0]
    assert parked.fsm.state == states.BUFFER_PREEMPTED
    assert parked.req is hl.req and parked.generated > 0
    slot = eng.slots[0]
    assert slot.request is not None
    assert slot.request.eff_priority == PRIORITY_HIGH
    assert all(p == -1 or eng.pool.refcount(p) >= 1
               for p in eng.pool.table(parked.req.req_id).pages)
    eng.step()                          # drain: high retires, low resumes
    assert hl.wait(timeout_s=60).fsm.state == states.REQUEST_COMPLETED
    assert not eng._parked


def test_engine_cancel_while_parked(engine_setup):
    cfg, model, params = engine_setup
    eng = _mk(model, params, overload=OverloadPolicy())
    hl = eng.connect(0).submit_i(np.arange(8) % cfg.vocab_size,
                                 max_tokens=16, priority=PRIORITY_LOW)
    for _ in range(3):
        eng.tick()
    eng.connect(1).submit_i((np.arange(6) + 3) % cfg.vocab_size,
                            max_tokens=8, priority=PRIORITY_HIGH)
    eng.tick()
    assert len(eng._parked) == 1
    assert hl.cancel()
    eng.step()
    rl = hl.wait(timeout_s=60)
    assert rl.fsm.state == states.REQUEST_CANCELLED
    assert len(rl.tokens_out) > 0       # partial output delivered
    assert not eng._parked
    assert eng.pool.free_pages() == eng.pool.n_pages


def test_engine_slo_shed(engine_setup):
    """SLO-aware shedding: a queued request past its deadline is shed
    with a typed falsy ShedStatus; one within deadline is served."""
    cfg, model, params = engine_setup
    eng = _mk(model, params,
              overload=OverloadPolicy(preemption=False, slo_s=1e-9))
    sess = eng.connect(0)
    h_shed = sess.submit_i(np.arange(4) % cfg.vocab_size, max_tokens=4)
    h_ok = sess.submit_i(np.arange(4) % cfg.vocab_size, max_tokens=4,
                         slo_s=300.0)   # per-request override
    eng.step()
    r_shed, r_ok = h_shed.wait(timeout_s=60), h_ok.wait(timeout_s=60)
    assert r_shed.fsm.state == states.REQUEST_CANCELLED
    assert isinstance(h_shed.status, ShedStatus) and not h_shed.status
    assert h_shed.status.slo_s == 1e-9
    assert len(r_shed.tokens_out) == 0
    assert r_ok.fsm.state == states.REQUEST_COMPLETED
    assert h_ok.status is None
    assert eng.stats["shed_requests"] == 1


def test_engine_no_starvation_under_high_flood(engine_setup):
    """Aging: a low-priority request beats a sustained high-priority
    flood into service — it does not wait for the flood to drain."""
    cfg, model, params = engine_setup
    eng = _mk(model, params, max_batch=2, pool_pages=32,
              overload=OverloadPolicy(aging_limit=2))
    s0, s1 = eng.connect(0), eng.connect(1)
    highs = [s0.submit_i(np.arange(4) % cfg.vocab_size, max_tokens=2,
                         priority=PRIORITY_HIGH) for _ in range(10)]
    low = s1.submit_i(np.arange(4) % cfg.vocab_size, max_tokens=2,
                      priority=PRIORITY_LOW)
    eng.step()
    rl = low.wait(timeout_s=60)
    assert rl.fsm.state == states.REQUEST_COMPLETED
    done_high = [h.wait(timeout_s=60) for h in highs]
    assert all(r.fsm.state == states.REQUEST_COMPLETED for r in done_high)
    # the low request finished before the flood's tail, not after it
    assert rl.done_t < max(r.done_t for r in done_high)


def test_engine_overload_off_is_unchanged(engine_setup):
    """overload=None keeps the legacy FIFO intake: priority argument is
    carried but ignored, counters stay zero."""
    cfg, model, params = engine_setup
    eng = _mk(model, params)
    h = eng.connect(0).submit_i(np.arange(4) % cfg.vocab_size, max_tokens=3,
                                priority=PRIORITY_HIGH, slo_s=1e-9)
    eng.step()
    r = h.wait(timeout_s=60)
    assert r.fsm.state == states.REQUEST_COMPLETED  # no shed without policy
    assert eng.stats["preemptions"] == 0
    assert eng.stats["shed_requests"] == 0
    assert isinstance(eng.intake, __import__("repro.core.host_queue",
                                             fromlist=["MpscQueue"]
                                             ).MpscQueue)
