"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Starts the lock-free ServeEngine and drives it with synthetic client
threads over the streaming session API: each client owns a Session,
submits through non-blocking ``submit_i`` handles, and consumes tokens
as they are produced via ``RequestHandle.tokens()``.  Per-client results
travel back to the main thread over private SPSC rings drained through
the Transport protocol — no lock anywhere in the demo, matching the
engine it demonstrates.  Prints throughput, completion latency, TTFT,
and the engine's lock-free stats.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time
from typing import Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.core import nbb
from repro.core.host_queue import SpscQueue
from repro.launch.compile_cache import place_compile_cache
from repro.models.model import build_model
from repro.serve.engine import ServeEngine
from repro.serve.overload import (
    PRIORITY_HIGH,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    OverloadPolicy,
)


def _pct(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return float("nan")
    return sorted_vals[min(int(len(sorted_vals) * q), len(sorted_vals) - 1)]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--requests-per-client", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="decode slots (default 8 for slot_paged — paged "
                         "residency is length-proportional, so slots are "
                         "cheap — else 4)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--scheduler", default=None,
                    choices=["slot_paged", "slot_chunked", "slot_fused",
                             "slot", "wave"],
                    help="slot_paged = chunked admission + fused decode "
                         "with the page pool as the device-resident KV "
                         "store (block-table indirection, zero-copy "
                         "residency; falls back to slot_chunked/"
                         "slot_fused for non-pageable archs); "
                         "slot_chunked = chunked zero-copy admission fused "
                         "into the decode micro-batch (default; falls back "
                         "to slot_fused for recurrent-state archs); "
                         "slot_fused = packet-mode fused K-step decode; "
                         "slot = per-token iteration-level batching; "
                         "wave = batch-level baseline")
    ap.add_argument("--k-max", type=int, default=8,
                    help="max fused decode steps per block (slot_fused/"
                         "slot_chunked)")
    ap.add_argument("--chunk-tokens", type=int, default=16,
                    help="prompt tokens streamed per dispatch "
                         "(slot_chunked)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the shared-prefix page cache "
                         "(slot_paged): every prompt prefills cold even "
                         "when its prefix KV is already resident")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="tokens of a common system prompt prepended to "
                         "every request (exercises the prefix cache)")
    ap.add_argument("--priorities", action="store_true",
                    help="enable the overload-control subsystem: requests "
                         "carry a priority class (~20%% high / 60%% normal "
                         "/ 20%% low) and intake serves classes strictly "
                         "with aging (DESIGN.md §12)")
    ap.add_argument("--preemption", action="store_true",
                    help="let a high-priority arrival preempt a running "
                         "low-priority slot by swapping its private KV "
                         "pages to host (slot_paged only; implies "
                         "--priorities)")
    ap.add_argument("--wfq", action="store_true",
                    help="weighted-fair queuing across clients inside "
                         "each priority class (implies --priorities)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="admission SLO: shed any request that waited "
                         "longer than this in the intake before binding "
                         "(implies --priorities)")
    ap.add_argument("--lease-s", type=float, default=None,
                    help="per-session lease: a client silent (no pump, "
                         "no submit) longer than this is presumed dead — "
                         "its in-flight requests fail with a typed "
                         "terminal and its slots/pages/rings are "
                         "reclaimed (DESIGN.md §13)")
    ap.add_argument("--tick-retries", type=int, default=1,
                    help="whole-tick retries the watchdog grants a "
                         "transient dispatch fault before failing the "
                         "bound slots (DESIGN.md §13)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="arm crash recovery (slot_paged only): "
                         "crash-consistent engine snapshots + a "
                         "write-ahead intake journal land here; "
                         "SIGINT/SIGTERM snapshot before exiting "
                         "(DESIGN.md §14)")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="periodic snapshot cadence in engine ticks "
                         "(default: only at shutdown/crash)")
    ap.add_argument("--restore", default=None, metavar="PATH",
                    help="restore before serving: a snapshot file, or a "
                         "snapshot directory (newest valid snapshot + "
                         "journal replay); prints the restore report")
    return ap


def build_engine(args: argparse.Namespace, seed: int = 0
                 ) -> Tuple[ServeEngine, Optional[OverloadPolicy],
                            Optional[str]]:
    """Config -> model -> random params from ``seed`` -> scheduler and
    pool sizing -> ``ServeEngine``, as the launcher's options ask.
    Returns the engine, its overload policy and its snapshot directory
    (each None when off)."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    scheduler = args.scheduler
    if scheduler is None:
        # Chunked admission needs position-indexed caches; recurrent
        # archs (mamba/rwkv) keep the fused monolithic-prefill default.
        scheduler = "slot_chunked" if model.chunkable else "slot_fused"
    if scheduler == "slot_paged" and not model.pageable:
        # Paged residency needs one uniform position-indexed KV shape.
        fallback = "slot_chunked" if model.chunkable else "slot_fused"
        print(f"{cfg.name}: not pageable, falling back to {fallback}")
        scheduler = fallback
    # Paged residency is length-proportional, so decode slots are cheap:
    # the paged default doubles the slot pool on the same HBM budget.
    max_batch = args.max_batch or (8 if scheduler == "slot_paged" else 4)
    page_size = 16
    if scheduler == "slot_paged":
        # The pool IS the device KV store: size it to exactly the dense
        # batch cache's position budget (max_batch * max_len) so the
        # kv-memory report below compares equal allocations — for the
        # dense schedulers the pool is accounting only, and its page
        # count is pure admission headroom.
        pool_pages = (max_batch * args.max_len + page_size - 1) // page_size
    else:
        pool_pages = max(256, args.clients * 16)
    overload = None
    use_overload = (args.priorities or args.preemption or args.wfq
                    or args.slo_ms is not None)
    if use_overload:
        preemption = args.preemption
        if preemption and scheduler != "slot_paged":
            # Page-swap preemption needs the page pool as the KV store.
            print(f"{scheduler}: no page pool, disabling --preemption")
            preemption = False
        overload = OverloadPolicy(
            priorities=True, preemption=preemption, wfq=args.wfq,
            slo_s=None if args.slo_ms is None else args.slo_ms / 1e3)
    snapshot_dir = args.snapshot_dir
    if args.restore is not None and snapshot_dir is None:
        # --restore implies a snapshot home: the directory the snapshot
        # lives in (so the journal opens alongside it).
        snapshot_dir = (args.restore if os.path.isdir(args.restore)
                        else os.path.dirname(args.restore) or ".")
    if snapshot_dir is not None and scheduler != "slot_paged":
        print(f"{scheduler}: no paged KV state, disabling snapshots")
        snapshot_dir = None
    eng = ServeEngine(model, params, max_batch=max_batch,
                      max_len=args.max_len, n_clients=args.clients,
                      pool_pages=pool_pages, page_size=page_size,
                      scheduler=scheduler, k_max=args.k_max,
                      chunk_tokens=min(args.chunk_tokens, args.max_len),
                      prefix_cache=not args.no_prefix_cache,
                      overload=overload, lease_s=args.lease_s,
                      tick_retries=args.tick_retries,
                      snapshot_dir=snapshot_dir,
                      snapshot_every=args.snapshot_every)
    return eng, overload, snapshot_dir


def serve_failure(eng: ServeEngine) -> Optional[str]:
    """Why a serve run must not count as a success, or None: the engine
    died, or it ended requests with a ``FailedStatus``."""
    if eng.dead is not None:
        return f"engine died: {eng.dead}"
    n = eng.stats["requests_failed"]
    if n:
        return f"{n} requests failed; first: {eng.first_fault}"
    return None


def main(argv=None) -> ServeEngine:
    args = build_parser().parse_args(argv)
    place_compile_cache()
    eng, overload, snapshot_dir = build_engine(args)
    cfg = eng.model.cfg
    scheduler = eng.scheduler
    if args.restore is not None and snapshot_dir is not None:
        report = (eng.restore_latest() if os.path.isdir(args.restore)
                  else eng.restore(args.restore))
        if report is None:
            print(f"restore: no usable snapshot under {args.restore}, "
                  f"starting empty")
        else:
            print(f"restore: resumed {report['resumed']} requests, "
                  f"replayed {report['replayed']}, "
                  f"redelivered {report['redelivered']} terminals, "
                  f"failed {report['failed']} "
                  f"(from {report.get('path', args.restore)})")

    # Graceful shutdown (DESIGN.md §14): SIGINT/SIGTERM stop the serve
    # loop, whose exit path snapshots the final consistent state — the
    # handler itself only sets flags (signal-safe).  Previous handlers
    # are restored on the way out so embedding callers keep theirs.
    prev_handlers = {}

    def _graceful(signum, frame):
        eng.request_snapshot()
        eng.stop()

    if snapshot_dir is not None:
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                prev_handlers[sig] = signal.signal(sig, _graceful)
            except ValueError:
                pass                    # not the main thread: skip
    eng_thread = eng.start()

    # One private SPSC result ring per client (client thread produces,
    # main thread drains after join): the Figure-1 fan-in without its
    # lock, in the launcher itself.
    results = [SpscQueue(args.requests_per_client + 1)
               for _ in range(args.clients)]

    # Optional shared system prompt: identical across every client, so
    # with the prefix cache on only the first prefill pays for it.
    shared = (np.arange(args.shared_prefix_len) * 7 + 3) % cfg.vocab_size

    def client(c: int) -> None:
        rng = np.random.default_rng(c)
        # Context-managed session: in-flight handles are cancelled and
        # the client's rings drop cleanly even when a client thread dies
        # mid-run (the robustness the lease reaper backstops server-side).
        with eng.connect(c) as session:
            for _ in range(args.requests_per_client):
                prompt = np.concatenate([
                    shared,
                    rng.integers(0, cfg.vocab_size, args.prompt_len)])
                # submit_i never blocks: a full intake ring just leaves
                # the handle PENDING and its own polling retries the send.
                if overload is not None:
                    u = rng.random()
                    pri = (PRIORITY_HIGH if u < 0.2
                           else PRIORITY_NORMAL if u < 0.8 else PRIORITY_LOW)
                    handle = session.submit_i(prompt,
                                              max_tokens=args.max_tokens,
                                              priority=pri)
                else:
                    handle = session.submit_i(prompt,
                                              max_tokens=args.max_tokens)
                n_stream = sum(1 for _ in handle.tokens(timeout_s=300))
                r = handle.response
                assert r is not None and n_stream == len(r.tokens_out)
                # Rejected/cancelled requests never produced a first
                # token; report their ttft as completion time like the
                # wave baseline.
                ttft_t = r.first_token_t or r.done_t
                status = results[c].send((r.done_t - r.submit_t,
                                          ttft_t - r.submit_t))
                assert status == nbb.OK     # sized to fit every result

    t0 = time.monotonic()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.monotonic() - t0
    eng.stop()
    eng_thread.join(timeout=10)
    for sig, h in prev_handlers.items():
        signal.signal(sig, h)
    if snapshot_dir is not None:
        print(f"crash recovery: {eng.stats['snapshots']} snapshots "
              f"({eng.stats['snapshot_bytes'] / 1024:.0f} KiB last), "
              f"{eng.stats['restores']} restores, "
              f"{eng.stats['replayed_requests']} replayed -> "
              f"{snapshot_dir}")

    lat, ttft = [], []
    for ring in results:                 # Transport-protocol drain
        for total_s, ttft_s in ring.drain():
            lat.append(total_s * 1e3)
            ttft.append(ttft_s * 1e3)
    lat.sort()
    ttft.sort()

    n = args.clients * args.requests_per_client
    toks = sum(args.max_tokens for _ in range(n))
    print(f"served {eng.stats['served']} requests in {dt:.2f}s "
          f"({n / dt:.1f} req/s, {toks / dt:.1f} tok/s)")
    print(f"latency ms: p50 {_pct(lat, 0.5):.0f} p95 {_pct(lat, 0.95):.0f}")
    print(f"ttft ms:    p50 {_pct(ttft, 0.5):.0f} p95 {_pct(ttft, 0.95):.0f}")
    print(f"engine stats: {eng.stats}")
    # Robustness report (DESIGN.md §13): what the self-healing machinery
    # actually did this run — all zeros unless a fault plan or lease was
    # armed, but printed whenever the knobs are on so the counters are
    # visible where operators look for them.
    if args.lease_s is not None or eng.faults is not None:
        fr = eng.fault_report()
        print(f"robustness: faults injected {fr['faults_injected']}  "
              f"requests failed {fr['requests_failed']}  "
              f"leases reaped {fr['leases_reaped']}  "
              f"pages quarantined {fr['pages_quarantined']}  "
              f"dead: {fr['dead'] or 'no'}")
    if scheduler != "wave":
        syncs_tok = eng.stats["host_syncs"] / max(toks, 1)
        print(f"slot occupancy: {eng.occupancy():.2f}  "
              f"host syncs/token: {syncs_tok:.2f}  "
              f"admission stall steps: "
              f"{eng.stats['admission_stall_steps']}  "
              f"oversize rejects: {len(eng.oversize_log)}  "
              f"kv pool: {eng.pool.stats()}")
    # KV-memory report (DESIGN.md §10): what residency actually cost.
    # Paged holds peak-resident page bytes and copies nothing; the dense
    # schedulers hold the full batch cache and pay admission copies.
    pstats = eng.pool.stats()
    dense_b = eng.dense_cache_bytes()
    resident = (pstats["kv_resident_bytes_peak"]
                if scheduler == "slot_paged" else dense_b)
    print(f"kv memory: resident {resident / 1024:.0f} KiB "
          f"(dense batch cache would be {dense_b / 1024:.0f} KiB, "
          f"{resident / max(dense_b, 1):.2f}x)  "
          f"kv copy traffic: {pstats['kv_copy_bytes'] / 1024:.0f} KiB")
    # Overload-control report (DESIGN.md §12): who waited, who got
    # swapped, who got shed — the honest cost of the priority tiers.
    if overload is not None:
        names = {PRIORITY_HIGH: "high", PRIORITY_NORMAL: "normal",
                 PRIORITY_LOW: "low"}
        for cls in sorted(eng.class_ttft()):
            c = eng.class_ttft()[cls]
            print(f"ttft[{names.get(cls, cls)}]: "
                  f"p50 {c['p50_ms']:.0f} p99 {c['p99_ms']:.0f} ms "
                  f"(n={c['n']})")
        print(f"overload: preemptions {eng.stats['preemptions']}  "
              f"resumes {eng.stats['resumes']}  "
              f"shed {eng.stats['shed_requests']}  "
              f"swap out {eng.stats['swap_out_bytes'] / 1024:.0f} KiB  "
              f"swap in {eng.stats['swap_in_bytes'] / 1024:.0f} KiB")
    # Prefix-sharing report (DESIGN.md §11): what the cache bought.
    if eng.prefix_cache is not None:
        cstats = eng.prefix_cache.stats()
        looked = cstats["hits"] + cstats["misses"]
        rate = cstats["hits"] / looked if looked else 0.0
        print(f"prefix cache: hit rate {rate:.2f} "
              f"({cstats['hits']}/{looked} lookups)  "
              f"prefill tokens saved {eng.stats['prefill_tokens_saved']}  "
              f"entries {cstats['entries']} "
              f"(evictions {cstats['evictions']})  "
              f"shared pages peak {pstats['shared_pages_peak']}  "
              f"cow copies {pstats['cow_copy_bytes'] / 1024:.0f} KiB")
    failure = serve_failure(eng)
    if failure is None and len(lat) < n:
        failure = f"{n - len(lat)} of {n} requests never reached a client"
    if failure is not None:
        print(f"serve failed: {failure}", file=sys.stderr)
        sys.exit(1)
    return eng


if __name__ == "__main__":
    main()
