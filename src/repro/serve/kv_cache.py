"""Paged KV-cache pool with a lock-free refcounted page allocator.

The serving engine's KV memory is a fixed pool of fixed-size pages (the
vLLM idea, TPU-adapted: each layer's share of a page is a few rows of
128-lane multiples, see :func:`pool_shape`).  Page accounting uses the
refcounted generalization of the paper's lock-free **bit set**
(refactoring step 3): claim-from-zero is a single CAS on a
:class:`RefCountArray` slot, share/release are wait-free fetch-add /
fetch-sub, and a page re-enters the free set exactly when its count hits
zero — so concurrent client threads admitting requests never serialize
behind a pool lock, and one physical page can back many sequences'
block-table rows at once.  Admission control stays non-blocking and
over-subscription is rejected with an explicit status (the NBB
BUFFER_FULL discipline) rather than a blocked caller.

Prefix sharing rides on the counts (DESIGN.md §11): the
:class:`PrefixCache` maps chained chunk-aligned prompt hashes to page
runs, admission increfs a hit's pages instead of dispatching prefill,
and a write into a page whose count exceeds one is gated behind
copy-on-write (``ensure_private``): claim a fresh page, device-copy that
one page, repoint the single block-table row, decref the shared
original.  CoW traffic is the only KV copying the paged scheduler ever
performs and is charged honestly to ``kv_copy_bytes`` (mirrored in
``cow_copy_bytes``).  Unreferenced cached prefixes stay resident as an
LRU set and are evicted under pool pressure before any claim fails.

Device-side, per-sequence KV lives scattered across the pool arrays.
Under the paged scheduler (``slot_paged``, DESIGN.md §10) the pool's
``k``/``v`` arrays ARE the device-resident KV store: decode attends
straight through per-slot block tables, and admission/retire only edit
int32 block-table rows and bitset pages.  The gather/scatter
``swap_in``/``swap_out`` pair is the copy-in/copy-out path that
indirection deletes — no scheduler calls it (it survives as the
measured baseline for tests/benchmarks), and every byte it or any
other residency copy moves is charged to the honest ``kv_copy_bytes``
counter, which stays 0 for ``slot_paged`` steady state.

The host-offload preemption tier that pair anticipated now exists
(DESIGN.md §12): ``swap_out_preempt`` parks a victim sequence by moving
its PRIVATE pages to host memory and releasing them — refcount>1 pages
(live prefix shares) are never moved or released, they stay resident for
their other holders and the victim keeps its references; the block-table
rows are parked as ``-1`` tombstones inside a :class:`SwapImage`.
``swap_in_preempt`` re-claims fresh pages all-or-nothing and scatters
the saved bytes back, so a resumed sequence is byte-identical to one
never preempted.  Swap traffic is charged to ``kv_copy_bytes`` and
itemized in ``swap_out_bytes``/``swap_in_bytes`` so the invariant
``kv_copy_bytes == cow_copy_bytes + swap_in_bytes + swap_out_bytes``
holds under ``slot_paged``.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_mod
from repro.core.refcount import RefCountArray

OK = 0
POOL_FULL = 1

LANES = 128                 # the TPU's vector width: a row of the pool


def row_group(page_size: int, kv_heads: int, head_dim: int) -> int:
    """Tokens per pool row: the least divisor ``g`` of ``page_size``
    whose ``g * kv_heads * head_dim`` is a multiple of 128, else
    ``page_size`` (one row per page)."""
    width = kv_heads * head_dim
    return next((g for g in range(1, page_size + 1)
                 if page_size % g == 0 and g * width % LANES == 0),
                page_size)


def pool_shape(n_pages: int, page_size: int, n_layers: int, kv_heads: int,
               head_dim: int) -> Tuple[int, int, int, int]:
    """Shape of the pool's k (and v) array: ``[n_layers, n_pages, rows,
    W]``, with ``g = row_group(...)``, ``rows = page_size // g`` and ``W =
    g * kv_heads * head_dim``.  Token ``t`` of a page sits in row ``t //
    g`` at lanes ``(t % g) * kv_heads * head_dim`` onward."""
    g = row_group(page_size, kv_heads, head_dim)
    return (n_layers, n_pages, page_size // g, g * kv_heads * head_dim)


def _take_pages(a: jax.Array, idx: jax.Array) -> jax.Array:
    """``a[:, idx]``, one page slice at a time: for a stablelm-3b pool
    (rows of 2560) XLA lowers the plain gather on axis 1 through
    temporaries the size of the whole pool."""
    out = jnp.zeros(a.shape[:1] + idx.shape + a.shape[2:], a.dtype)
    return jax.lax.fori_loop(
        0, idx.shape[0],
        lambda i, o: jax.lax.dynamic_update_slice_in_dim(
            o, jax.lax.dynamic_slice_in_dim(a, idx[i], 1, axis=1), i,
            axis=1), out)


@jax.jit
def pool_read_pages(k, v, idx):
    """Pages ``idx`` of the pool arrays, ``[L, len(idx), rows, W]``."""
    return _take_pages(k, idx), _take_pages(v, idx)


def _copy_pages_program(n: int):
    """The donated program copying ``n`` whole pages inside the pool
    arrays (pages are axis 1)."""
    def copy_pages(k, v, s, d):
        return (k.at[:, d].set(_take_pages(k, s)),
                v.at[:, d].set(_take_pages(v, s)))

    # Named, so that a profiler trace shows which program it is.
    copy_pages.__name__ = f"pool_copy_pages_n{n}"
    return jax.jit(copy_pages, donate_argnums=(0, 1))


def _write_pages_program(n: int):
    """The donated program writing ``n`` host page images (``[n, L,
    rows, W]``) into pages ``d`` of the pool arrays."""
    def write_pages(k, v, d, kh, vh):
        return (k.at[:, d].set(jnp.swapaxes(kh, 0, 1)),
                v.at[:, d].set(jnp.swapaxes(vh, 0, 1)))

    write_pages.__name__ = f"pool_write_pages_n{n}"
    return jax.jit(write_pages, donate_argnums=(0, 1))


@dataclasses.dataclass
class PageTable:
    """Host-side metadata for one sequence's pages.

    ``slot`` is the decode slot the sequence is bound to in the slot-swap
    engine (None for wave scheduling / unbound sequences); ``n_reserved``
    records the admission-time reservation so utilization stats can report
    how much of the reservation a sequence actually consumed.
    """
    seq_id: int
    pages: List[int]
    n_tokens: int = 0
    slot: Optional[int] = None
    n_reserved: int = 0


@dataclasses.dataclass
class SwapImage:
    """Host-side parking record for one preempted sequence (DESIGN.md
    §12).  ``rows`` are the block-table rows whose private live pages
    were gathered into ``k``/``v`` (numpy page images from
    :meth:`PagedKVPool.read_pages`, one entry per row, in row order);
    ``dead_rows`` were reserved-ahead (never-attended) pages released
    without copying; ``shared_rows`` are refcount>1 prefix
    pages that never moved — the sequence keeps its references and the
    block-table rows stay valid while parked."""
    seq_id: int
    rows: List[int]
    k: "np.ndarray"
    v: "np.ndarray"
    dead_rows: List[int]
    shared_rows: List[int]

    @property
    def nbytes(self) -> int:
        return int(self.k.nbytes) + int(self.v.nbytes)


class PagedKVPool:
    """One pool per (layer-stacked) KV tensor family.

    k/v pools: ``pool_shape(...)`` = [n_layers, n_pages, rows, W] —
    layer-major, and one page holds all layers for a span of
    ``page_size`` tokens, so a sequence needs ceil(len/page_size) pages
    total (not per layer).  A row packs ``g = row_group(...)`` tokens'
    ``kv_heads * head_dim`` values into a width ``W`` that is a multiple
    of 128 where a divisor of ``page_size`` allows it.  The layout is the
    device's own: for a minor axis of ``head_dim`` under 128 the TPU
    runtime keeps the page axis minor, so every step program began and
    ended by relayouting the whole pool, and some did so per layer.
    Pages are indexed on axis 1 here only (:meth:`read_pages`,
    :meth:`write_pages`, the copy program); host images hold one entry
    per page, in page order.
    """

    def __init__(self, n_pages: int, page_size: int, n_layers: int,
                 kv_heads: int, head_dim: int, dtype=jnp.bfloat16):
        self.n_pages, self.page_size = n_pages, page_size
        self.n_layers, self.kv_heads, self.head_dim = (n_layers, kv_heads,
                                                       head_dim)
        shape = pool_shape(n_pages, page_size, n_layers, kv_heads, head_dim)
        self.page_shape = (n_layers,) + shape[2:]   # one page's host image
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        self._alloc = RefCountArray(n_pages)
        self._tables: Dict[int, PageTable] = {}
        self._next_probe = 0
        # Honest KV-traffic counters (DESIGN.md §10): every byte a
        # scheduler moves to (re)establish residency is charged here —
        # swap_in/swap_out page traffic and the engine's dense
        # cache-admission copies.  The paged scheduler's steady state
        # performs no KV copies at all, so its counter stays 0 until a
        # copy-on-write fires (``cow_copy_bytes`` isolates that share).
        self.kv_copy_bytes = 0
        self.cow_copy_bytes = 0
        self.swap_in_bytes = 0
        self.swap_out_bytes = 0
        self._peak_pages = 0
        self._shared_peak = 0
        # Pool-pressure escape hatch: the prefix cache registers its LRU
        # evictor here so resident-but-unreferenced prefixes yield their
        # pages before any claim fails (DESIGN.md §11).
        self._evict: Optional[Callable[[], bool]] = None
        self._cow_fns: Dict[int, Callable] = {}
        self._swap_fns: Dict[int, Callable] = {}
        # Fault-injection plan (DESIGN.md §13): armed by the engine;
        # every probe below is one ``is None`` check when disarmed.
        self.faults: Optional["faults_mod.FaultPlan"] = None
        # Pages implicated in a failed/poisoned write, pinned out of
        # circulation: quarantine holds one extra reference, so when the
        # owning sequence frees, the count lands at 1 — never 0 — and
        # claim-from-zero can never hand the page out again.
        self.quarantined: set = set()

    # -- allocation (lock-free) ------------------------------------------------
    def pages_needed(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.page_size))

    def _claim_pages(self, n: int) -> Optional[List[int]]:
        """THE page-claim loop (every reservation path goes through it):
        claim ``n`` pages lock-free, all-or-nothing — on shortage the
        partial claim is rolled back and None returned, so concurrent
        admitters can't deadlock each other or strand half-claims."""
        if (n > 0 and self.faults is not None
                and self.faults.fire("pool.claim") is not None):
            return None                 # injected shortage: pre-claim, clean
        got: List[int] = []
        for _ in range(n):
            while True:
                # fresh token per claim: setdefault-CAS must not recognize
                # our own earlier claims as "won again"
                page = self._alloc.try_claim(owner=object(),
                                             start=self._next_probe)
                if page is not None:
                    break
                # Pool pressure: evict an unreferenced cached prefix and
                # retry before declaring shortage.  Eviction only drops
                # the cache's references, so a page still backing a live
                # sequence never leaves the pool here.
                if self._evict is None or not self._evict():
                    for p in got:  # roll back — nobody waits on us
                        self._alloc.release(p)
                    return None
            self._next_probe = (page + 1) % self.n_pages
            got.append(page)
        self._peak_pages = max(self._peak_pages, self.used_pages())
        return got

    def set_pressure_callback(self,
                              evict: Optional[Callable[[], bool]]) -> None:
        """Install the evict-one-prefix-under-pressure hook (returns True
        when it released something worth retrying the claim for)."""
        self._evict = evict

    @property
    def page_nbytes(self) -> int:
        """Device bytes one page occupies across both pool arrays."""
        return 2 * math.prod(self.page_shape) * self.k.dtype.itemsize

    def reset_traffic(self) -> None:
        """Zero the copy/peak counters (benchmark pass boundaries)."""
        self.kv_copy_bytes = 0
        self.cow_copy_bytes = 0
        self.swap_in_bytes = 0
        self.swap_out_bytes = 0
        self._peak_pages = self.used_pages()
        self._shared_peak = self._alloc.shared_count()

    def try_admit(self, seq_id: int, n_tokens: int,
                  slot: Optional[int] = None) -> int:
        """Claim pages for a sequence.  OK or POOL_FULL (all-or-nothing).
        ``slot`` binds the reservation to a decode slot for per-slot
        accounting."""
        got = self._claim_pages(self.pages_needed(n_tokens))
        if got is None:
            return POOL_FULL
        self._tables[seq_id] = PageTable(seq_id, got, n_tokens, slot=slot,
                                         n_reserved=n_tokens)
        return OK

    # -- prefix sharing (refcounts + copy-on-write, DESIGN.md §11) -------------
    def adopt_shared(self, seq_id: int, pages: List[int], n_tokens: int,
                     slot: Optional[int] = None) -> None:
        """Admit a sequence onto already-resident prefix pages: one incref
        per page and an int32 block-table row — no device dispatch, no
        claim that can fail.  ``n_tokens`` is the prefix extent the pages
        cover (the sequence resumes prefill there)."""
        for p in pages:
            self._alloc.incref(p)
        self._tables[seq_id] = PageTable(seq_id, list(pages), n_tokens,
                                         slot=slot, n_reserved=n_tokens)
        self._note_sharing()
        self._peak_pages = max(self._peak_pages, self.used_pages())

    def incref_pages(self, pages: List[int]) -> None:
        """Take one reference per page (prefix-cache residency)."""
        for p in pages:
            self._alloc.incref(p)
        self._note_sharing()

    def decref_pages(self, pages: List[int]) -> None:
        """Drop one reference per page; pages whose count reaches zero
        re-enter the free set (prefix-cache eviction)."""
        for p in pages:
            self._alloc.decref(p)

    def refcount(self, page: int) -> int:
        return self._alloc.refcount(page)

    def _note_sharing(self) -> None:
        self._shared_peak = max(self._shared_peak,
                                self._alloc.shared_count())

    def ensure_private(self, seq_id: int, start_pos: int,
                       end_pos: int) -> int:
        """Copy-on-write gate: before a dispatch writes KV positions
        ``[start_pos, end_pos)`` of a sequence, repoint every page in
        that range that another holder can still read.  Per shared page:
        claim a fresh page, device-copy exactly that page, swap the one
        block-table row, decref the original (which stays resident for
        its other holders).  All-or-nothing like every claim path."""
        if end_pos <= start_pos:
            return OK
        t = self._tables[seq_id]
        ps = self.page_size
        first = start_pos // ps
        last = min((end_pos - 1) // ps, len(t.pages) - 1)
        rows = [i for i in range(first, last + 1)
                if self._alloc.refcount(t.pages[i]) > 1]
        if not rows:
            return OK
        if (self.faults is not None
                and self.faults.fire("pool.cow") is not None):
            return POOL_FULL            # injected: before any claim or copy
        fresh = self._claim_pages(len(rows))
        if fresh is None:
            return POOL_FULL
        self._copy_pages([t.pages[i] for i in rows], fresh)
        nbytes = len(rows) * self.page_nbytes
        self.kv_copy_bytes += nbytes
        self.cow_copy_bytes += nbytes
        for i, new_p in zip(rows, fresh):
            old = t.pages[i]
            t.pages[i] = new_p
            self._alloc.decref(old)
        return OK

    def _copy_pages(self, src: List[int], dst: List[int]) -> None:
        """One fused device dispatch copying len(src) whole pages inside
        the pool arrays (donated, so XLA updates in place)."""
        fn = self._cow_fns.get(len(src))
        if fn is None:
            fn = self._cow_fns[len(src)] = _copy_pages_program(len(src))
        s = jnp.asarray(src, jnp.int32)
        d = jnp.asarray(dst, jnp.int32)
        self.k, self.v = fn(self.k, self.v, s, d)

    def note_tokens(self, seq_id: int, n_tokens: int) -> None:
        """Record decode growth inside the existing reservation (no page
        traffic; keeps per-slot utilization stats truthful)."""
        self._tables[seq_id].n_tokens = n_tokens

    def extend_reservation(self, seq_id: int, n_tokens: int) -> int:
        """Grow a sequence's *reservation* to cover ``n_tokens`` without
        recording them as written (chunked admission, DESIGN.md §9: pages
        are claimed chunk by chunk as prompt positions materialize, then
        the decode budget is reserved with the final chunk).  All-or-
        nothing, so a mid-stream admission under memory pressure aborts
        cleanly instead of holding half its pages.  ``note_tokens``
        still reports actual written growth."""
        t = self._tables[seq_id]
        need = self.pages_needed(n_tokens) - len(t.pages)
        if (need > 0 and self.faults is not None
                and self.faults.fire("pool.extend") is not None):
            return POOL_FULL            # injected: table untouched
        got = self._claim_pages(need)
        if got is None:
            return POOL_FULL
        t.pages.extend(got)
        t.n_reserved = max(t.n_reserved, n_tokens)
        return OK

    def grow(self, seq_id: int, new_n_tokens: int) -> int:
        """Extend a sequence (decode appends); claims pages as needed."""
        t = self._tables[seq_id]
        got = self._claim_pages(self.pages_needed(new_n_tokens)
                                - len(t.pages))
        if got is None:
            return POOL_FULL
        t.pages.extend(got)
        t.n_tokens = new_n_tokens
        return OK

    def free(self, seq_id: int) -> None:
        t = self._tables.pop(seq_id)
        for p in t.pages:
            if p >= 0:  # skip swap tombstones of a parked sequence
                self._alloc.release(p)

    def quarantine_range(self, seq_id: int, start_pos: int,
                         end_pos: int) -> List[int]:
        """Remove the pages backing positions ``[start_pos, end_pos)`` of
        a sequence from circulation after a failed/poisoned write
        (DESIGN.md §13).  Only PRIVATE pages (refcount == 1) are pinned:
        a shared page's bytes predate the failed write — other holders
        adopted it from a committed prefix — so it is provably clean.
        Pinning is one incref; the page is permanently accounted as used
        (``free_pages`` stays exact) and, because claims only win on
        count zero, it can never back a future sequence.  Idempotent per
        page.  Returns the pages quarantined by THIS call."""
        t = self._tables.get(seq_id)
        if t is None or end_pos <= start_pos:
            return []
        ps = self.page_size
        first = max(0, start_pos // ps)
        last = min((end_pos - 1) // ps, len(t.pages) - 1)
        got: List[int] = []
        for i in range(first, last + 1):
            p = t.pages[i]
            if (p >= 0 and p not in self.quarantined
                    and self._alloc.refcount(p) == 1):
                self._alloc.incref(p)
                self.quarantined.add(p)
                got.append(p)
        return got

    def free_pages(self) -> int:
        return self.n_pages - self._alloc.count()

    def used_pages(self) -> int:
        return self._alloc.count()

    def n_seqs(self) -> int:
        return len(self._tables)

    def table(self, seq_id: int) -> PageTable:
        return self._tables[seq_id]

    def stats(self) -> Dict[str, object]:
        """Snapshot for occupancy/utilization reporting: overall page use
        plus a per-slot breakdown {slot: (pages, tokens, reserved)}."""
        per_slot = {
            t.slot: (len(t.pages), t.n_tokens, t.n_reserved)
            for t in self._tables.values() if t.slot is not None
        }
        return {"n_pages": self.n_pages, "used": self.used_pages(),
                "free": self.free_pages(), "seqs": self.n_seqs(),
                "per_slot": per_slot,
                # Length-proportional residency (DESIGN.md §10): bytes
                # of pool pages the live sequences actually hold (and
                # the high-water mark), vs the dense batch cache's fixed
                # O(B * max_len) — plus every byte any scheduler spent
                # COPYING KV to establish residency (0 for slot_paged).
                # Under sharing, ``used_pages`` counts each *physical*
                # page once however many block-table rows point at it —
                # residency reflects HBM actually held, not the sum of
                # per-sequence views.
                "kv_resident_bytes": self.used_pages() * self.page_nbytes,
                "kv_resident_bytes_peak": self._peak_pages * self.page_nbytes,
                "kv_copy_bytes": self.kv_copy_bytes,
                "cow_copy_bytes": self.cow_copy_bytes,
                "swap_in_bytes": self.swap_in_bytes,
                "swap_out_bytes": self.swap_out_bytes,
                "shared_pages": self._alloc.shared_count(),
                "shared_pages_peak": self._shared_peak,
                "quarantined": len(self.quarantined)}

    # -- device data movement (RETIRED: no scheduler calls these) ---------------
    # Residency under ``slot_paged`` is established by writing int32
    # block-table rows, not by moving HBM.  The pair remains only as
    # the measured "what the block table deletes" baseline
    # (benchmarks/bench_kernels.py, tests) and as the copy hook a
    # host-offload preemption tier would charge to ``kv_copy_bytes``.
    def swap_in(self, seq_id: int, max_len: int
                ) -> Tuple[jax.Array, jax.Array]:
        """Gather a sequence's pages -> contiguous [max_len, L, kv, hd] k/v."""
        t = self._tables[seq_id]
        self.kv_copy_bytes += len(t.pages) * self.page_nbytes
        k, v = map(self._token_major, pool_read_pages(
            self.k, self.v, jnp.asarray(t.pages, jnp.int32)))
        pad = max_len - k.shape[0]
        if pad > 0:
            zk = jnp.zeros((pad,) + k.shape[1:], k.dtype)
            k, v = jnp.concatenate([k, zk]), jnp.concatenate([v, zk])
        return k[:max_len], v[:max_len]

    def swap_out(self, seq_id: int, k_seq: jax.Array, v_seq: jax.Array,
                 n_tokens: int) -> int:
        """Scatter contiguous [S, L, kv, hd] k/v back into the pool."""
        status = self.grow(seq_id, n_tokens)
        if status != OK:
            return status
        t = self._tables[seq_id]
        ps = self.page_size
        n_pages = self.pages_needed(n_tokens)
        self.kv_copy_bytes += n_pages * self.page_nbytes
        pad = n_pages * ps - k_seq.shape[0]
        if pad > 0:
            zk = jnp.zeros((pad,) + k_seq.shape[1:], k_seq.dtype)
            k_seq = jnp.concatenate([k_seq, zk])
            v_seq = jnp.concatenate([v_seq, zk])
        idx = jnp.asarray(t.pages[:n_pages], jnp.int32)
        self.k = self.k.at[:, idx].set(self._page_major(k_seq[:n_pages * ps]))
        self.v = self.v.at[:, idx].set(self._page_major(v_seq[:n_pages * ps]))
        return OK

    def _token_major(self, x: jax.Array) -> jax.Array:
        """Pool pages ``[L, n, rows, W]`` -> tokens ``[n*ps, L, kv, hd]``."""
        L, n, rows, _ = x.shape
        g = self.page_size // rows
        x = x.reshape(L, n, rows, g, self.kv_heads, self.head_dim)
        return x.transpose(1, 2, 3, 0, 4, 5).reshape(
            n * self.page_size, L, self.kv_heads, self.head_dim)

    def _page_major(self, x: jax.Array) -> jax.Array:
        """Tokens ``[n*ps, L, kv, hd]`` -> pool pages ``[L, n, rows, W]``."""
        L, rows, W = self.page_shape
        n = x.shape[0] // self.page_size
        x = x.reshape(n, rows, W // (self.kv_heads * self.head_dim), L,
                      self.kv_heads, self.head_dim)
        return x.transpose(3, 0, 1, 2, 4, 5).reshape(L, n, rows, W)

    # -- page-swap preemption (the overload tier, DESIGN.md §12) -------------
    def swap_out_preempt(self, seq_id: int, n_live_tokens: int) -> SwapImage:
        """Park a sequence host-side, releasing its pool pages.

        Page disposition, by rule not position:
          * refcount > 1 (live prefix shares) — NEVER moved or
            released; the victim keeps its references and the rows stay
            valid, so a preempted prefix-cache hit leaves the shared
            pages resident and ``cow_copy_bytes`` untouched.
          * refcount == 1, row < live extent — gathered to host in one
            indexed read, then released (``-1`` tombstone in the row).
          * refcount == 1, row >= live extent (reserved-ahead pages no
            token was ever written to) — released without copying;
            resume re-claims them blank, and positions past the live
            extent are never attended before being rewritten.

        Only the copied bytes are charged (``swap_out_bytes``, mirrored
        into ``kv_copy_bytes``).  The caller parks the returned image
        with its BUFFER_PREEMPTED cell and later hands it back to
        :meth:`swap_in_preempt`.
        """
        if (self.faults is not None
                and self.faults.fire("pool.swap_out") is not None):
            # Raised before ANY mutation: the victim's pages, table and
            # counters are untouched, so the engine treats this exactly
            # like "no preemptible victim" and the needer takes the
            # ordinary rejection path.
            raise faults_mod.InjectedFault("pool.swap_out",
                                           self.faults.n_fired,
                                           retryable=True)
        t = self._tables[seq_id]
        live = 0 if n_live_tokens <= 0 else self.pages_needed(n_live_tokens)
        rows: List[int] = []
        dead_rows: List[int] = []
        shared_rows: List[int] = []
        for i, p in enumerate(t.pages):
            if p < 0:
                continue
            if self._alloc.refcount(p) > 1:
                shared_rows.append(i)
            elif i < live:
                rows.append(i)
            else:
                dead_rows.append(i)
        k_host, v_host = self.read_pages([t.pages[i] for i in rows])
        for i in rows + dead_rows:
            self._alloc.release(t.pages[i])
            t.pages[i] = -1
        nbytes = len(rows) * self.page_nbytes
        self.swap_out_bytes += nbytes
        self.kv_copy_bytes += nbytes
        t.slot = None
        return SwapImage(seq_id, rows, k_host, v_host, dead_rows,
                         shared_rows)

    def swap_in_preempt(self, seq_id: int, image: SwapImage) -> int:
        """Re-establish a parked sequence's residency: claim fresh pages
        for every tombstoned row (all-or-nothing — POOL_FULL leaves the
        image and table untouched for a later retry), scatter the saved
        bytes back in one fused donated dispatch, and leave the shared
        rows alone (they never left).  The resumed sequence reads back
        byte-identical: pages moved wholesale, and the block-table
        indirection makes the new physical page numbers invisible."""
        if (self.faults is not None
                and self.faults.fire("pool.swap_in") is not None):
            return POOL_FULL            # injected: image stays parked
        t = self._tables[seq_id]
        need = len(image.rows) + len(image.dead_rows)
        got = self._claim_pages(need)
        if got is None:
            return POOL_FULL
        for i, p in zip(image.rows + image.dead_rows, got):
            t.pages[i] = p
        if image.rows:
            self.write_pages(got[:len(image.rows)], image.k, image.v)
        nbytes = len(image.rows) * self.page_nbytes
        self.swap_in_bytes += nbytes
        self.kv_copy_bytes += nbytes
        self._peak_pages = max(self._peak_pages, self.used_pages())
        return OK

    # -- crash-recovery snapshots (DESIGN.md §14) ------------------------------
    def snapshot_state(self, extra_pages=()) -> Dict[str, object]:
        """Host-side image of the pool at a tick boundary: block tables,
        per-page refcounts, quarantine pins, traffic counters, and the
        bytes of every page that backs *written* positions — gathered
        once per distinct physical page in a single device fetch, so a
        page shared by N sequences (or pinned by a prefix-cache entry)
        costs one page of host memory, not N.  Reserved-ahead pages
        (rows beyond ``pages_needed(n_tokens)``) carry no bytes: nothing
        attended lives there, so restore re-claims them blank.
        ``extra_pages`` lets the engine pin prefix-cache-resident pages
        whose owning sequences have already retired."""
        refcounts = {p: self._alloc.refcount(p) for p in range(self.n_pages)
                     if self._alloc.refcount(p) > 0}
        tables = {sid: {"pages": list(t.pages), "n_tokens": t.n_tokens,
                        "slot": t.slot, "n_reserved": t.n_reserved}
                  for sid, t in self._tables.items()}
        need = {int(p) for p in extra_pages}
        for t in self._tables.values():
            if t.n_tokens > 0:
                live = min(self.pages_needed(t.n_tokens), len(t.pages))
                need.update(p for p in t.pages[:live] if p >= 0)
        idx = sorted(need)
        k_host, v_host = self.read_pages(idx)
        return {
            "n_pages": self.n_pages, "page_size": self.page_size,
            "refcounts": refcounts, "tables": tables,
            "quarantined": set(self.quarantined),
            "next_probe": self._next_probe,
            "counters": {
                "kv_copy_bytes": self.kv_copy_bytes,
                "cow_copy_bytes": self.cow_copy_bytes,
                "swap_in_bytes": self.swap_in_bytes,
                "swap_out_bytes": self.swap_out_bytes,
                "peak_pages": self._peak_pages,
                "shared_peak": self._shared_peak,
            },
            "data_pages": idx, "k": k_host, "v": v_host,
        }

    def reset(self) -> None:
        """Return the pool to its just-constructed state: zeroed device
        arrays (stale bytes from a previous incarnation must not leak
        into restored sequences), a fresh allocator, no tables, no
        quarantine, zero counters.  Compiled CoW/swap traces survive."""
        shape = self.k.shape
        dtype = self.k.dtype
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        self._alloc = RefCountArray(self.n_pages)
        self._tables = {}
        self._next_probe = 0
        self.quarantined = set()
        self.kv_copy_bytes = 0
        self.cow_copy_bytes = 0
        self.swap_in_bytes = 0
        self.swap_out_bytes = 0
        self._peak_pages = 0
        self._shared_peak = 0

    def restore_state(self, state: Dict[str, object]) -> None:
        """Rebuild the pool from a :meth:`snapshot_state` image.  Every
        physical page id is re-claimed at its exact saved refcount via
        ``RefCountArray.claim_specific`` — block tables restore verbatim,
        so post-restore decode reads the same page numbers the snapshot
        recorded.  Saved page bytes scatter back in one fused dispatch
        (reusing the swap-in trace cache); traffic counters restore to
        their snapshotted values, so the copy ledger stays exact across
        the restart (the restore scatter itself is recovery traffic, not
        scheduler traffic, and is deliberately not charged)."""
        if (state["n_pages"] != self.n_pages
                or state["page_size"] != self.page_size):
            raise ValueError(
                f"pool shape mismatch: snapshot {state['n_pages']}p x "
                f"{state['page_size']}, pool {self.n_pages}p x "
                f"{self.page_size}")
        self.reset()
        for p, n in state["refcounts"].items():
            if not self._alloc.claim_specific(p):
                raise RuntimeError(f"page {p} not claimable on restore")
            for _ in range(n - 1):
                self._alloc.incref(p)
        self._tables = {
            sid: PageTable(sid, list(d["pages"]), d["n_tokens"],
                           slot=d["slot"], n_reserved=d["n_reserved"])
            for sid, d in state["tables"].items()}
        self.quarantined = set(state["quarantined"])
        self._next_probe = state["next_probe"]
        c = state["counters"]
        self.kv_copy_bytes = c["kv_copy_bytes"]
        self.cow_copy_bytes = c["cow_copy_bytes"]
        self.swap_in_bytes = c["swap_in_bytes"]
        self.swap_out_bytes = c["swap_out_bytes"]
        self._peak_pages = c["peak_pages"]
        self._shared_peak = c["shared_peak"]
        idx = state["data_pages"]
        if idx:
            self.write_pages(idx, state["k"], state["v"])

    # -- page accessors: the only code that knows the pages' axis -------------
    def read_pages(self, pages: List[int]) -> Tuple[np.ndarray, np.ndarray]:
        """Host images of whole pages, ``[len(pages), *page_shape]`` for
        k and v, in the order given: one device fetch."""
        if not len(pages):
            empty = np.zeros((0,) + self.page_shape, self.k.dtype)
            return empty, empty
        k, v = pool_read_pages(self.k, self.v,
                               jnp.asarray(pages, jnp.int32))
        return (np.ascontiguousarray(np.asarray(k).swapaxes(0, 1)),
                np.ascontiguousarray(np.asarray(v).swapaxes(0, 1)))

    def write_pages(self, dst: List[int], k_host, v_host) -> None:
        """One fused device dispatch writing host images of whole pages
        (as :meth:`read_pages` returns them) into pages ``dst`` of the
        pool arrays (donated, so XLA updates in place)."""
        fn = self._swap_fns.get(len(dst))
        if fn is None:
            fn = self._swap_fns[len(dst)] = _write_pages_program(len(dst))
        self.k, self.v = fn(self.k, self.v, jnp.asarray(dst, jnp.int32),
                            jnp.asarray(k_host), jnp.asarray(v_host))


@dataclasses.dataclass
class PrefixEntry:
    """One cached chunk-aligned prompt prefix: ``pages`` cover the first
    ``n_tokens`` positions of the (bucketed, left-padded) token stream
    whose chained chunk hash is ``key``.  The entry holds one reference
    per page, so the pages stay resident after every sequence that wrote
    or shared them has retired."""
    key: int
    n_tokens: int
    pages: List[int]
    tick: int = 0


class PrefixCache:
    """LRU map from chained chunk hashes to resident page runs.

    Hashes are chained (each chunk's hash folds in its predecessor's),
    so an entry for a shallow prefix of a cached deep prefix is its own
    key — a lookup walks candidate depths deepest-first and the first
    present entry wins, which is how "a prefix of a cached prefix also
    hits".  The cache registers its LRU evictor as the pool's pressure
    callback: page claims evict unreferenced prefixes before failing.
    """

    def __init__(self, pool: PagedKVPool):
        self.pool = pool
        self._entries: Dict[int, PrefixEntry] = {}
        self._clock = itertools.count()
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0
        pool.set_pressure_callback(self.evict_lru)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: int) -> bool:
        return key in self._entries

    def insert(self, key: int, n_tokens: int, pages: List[int]) -> bool:
        """Cache a prefix: one incref per page (the cache's own
        residency).  Idempotent per key — re-inserting bumps LRU only."""
        ent = self._entries.get(key)
        if ent is not None:
            ent.tick = next(self._clock)
            return False
        self.pool.incref_pages(pages)
        self._entries[key] = PrefixEntry(key, n_tokens, list(pages),
                                         next(self._clock))
        self.insertions += 1
        return True

    def lookup(self, keys: List[int]) -> Optional[PrefixEntry]:
        """Deepest-first probe: ``keys`` are chained hashes ordered
        deepest prefix first; the first cached one wins."""
        for key in keys:
            ent = self._entries.get(key)
            if ent is not None:
                ent.tick = next(self._clock)
                self.hits += 1
                return ent
        self.misses += 1
        return None

    def evict_key(self, key: int) -> bool:
        """Drop one entry's references (LRU eviction and the engine's
        abort rollback).  Pages no live sequence shares return to the
        free set; pages still backing sequences merely lose the cache's
        claim on them — never freed out from under a holder."""
        ent = self._entries.pop(key, None)
        if ent is None:
            return False
        self.pool.decref_pages(ent.pages)
        self.evictions += 1
        return True

    def evict_lru(self) -> bool:
        """Evict the least-recently-used prefix (pool-pressure hook)."""
        if not self._entries:
            return False
        return self.evict_key(
            min(self._entries, key=lambda k: self._entries[k].tick))

    def clear(self) -> None:
        while self.evict_lru():
            pass

    def resident_pages(self) -> set:
        """Physical pages the cache holds references on (each once)."""
        out: set = set()
        for ent in self._entries.values():
            out.update(ent.pages)
        return out

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses, "insertions": self.insertions,
                "evictions": self.evictions,
                "resident_pages": len(self.resident_pages())}
