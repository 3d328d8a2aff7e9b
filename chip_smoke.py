"""On-chip smoke run of the serving main path, on one TPU chip.

    python chip_smoke.py [--seed N]

Builds smollm-135m at its published widths, with random weights drawn
from ``--seed``, the way ``python -m repro.launch.serve`` builds it: the
paged scheduler with the prefix cache on, max_len 2048, 8 decode slots,
16-token pages.  It compiles every program the engine can dispatch, then
sends 4 sessions x 3 requests through the public session API
(``connect`` -> ``submit_i`` -> ``handle.tokens()``).  Half of the
requests share one 256-token system prompt, so prefix hits and
copy-on-write page copies happen; one request is cancelled mid-decode.

The run fails, printing why and no ``"ok": true`` line, unless:

* every request that was not cancelled completes with exactly 32 tokens,
  and the cancelled one ends cancelled with fewer;
* no request failed, the tick watchdog caught nothing, and the engine
  is alive;
* after stop, the pool holds exactly the pages the prefix cache holds;
* every engine token's logit, under a cache-free ``model.forward`` over
  the same token stream, lies within 2% of that row's logit range of
  the row maximum, and at least 90% of the tokens are that row's argmax.

Everything, the reference included, runs in this one process.  Without
a TPU it prints the platform it found and exits 1.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

ARCH = "smollm-135m"
MAX_LEN = 2048
MAX_BATCH = 8
# Chunks of half a page: a prefix hit then ends mid-page, and the hitter
# copies that page before writing it (with page-sized chunks every hit
# is page-aligned and copy-on-write never runs).
CHUNK_TOKENS = 8
N_SESSIONS = 4
PER_SESSION = 3
MAX_TOKENS = 32
SYSTEM_LEN = 256
MIN_PROMPT, MAX_PROMPT = 64, 1024
CANCEL = (N_SESSIONS - 1, PER_SESSION - 1)     # (session, request)
TOL_FRACTION = 0.02         # of each row's logit range (max - min)
MIN_AGREEMENT = 0.9


class SmokeFailure(Exception):
    pass


def launcher_argv(smoke: bool = False) -> list:
    """The ``repro.launch.serve`` options this run builds its engine
    from (``smoke`` only for a rehearsal on the CPU)."""
    argv = ["--arch", ARCH, "--scheduler", "slot_paged",
            "--max-len", str(MAX_LEN), "--max-batch", str(MAX_BATCH),
            "--clients", str(N_SESSIONS), "--max-tokens", str(MAX_TOKENS),
            "--chunk-tokens", str(CHUNK_TOKENS)]
    return argv + (["--smoke"] if smoke else [])


def make_traffic(rng: np.random.Generator, vocab: int) -> list:
    """``N_SESSIONS`` lists of ``PER_SESSION`` prompts.  Every other
    request opens with the shared system prompt.  The engine left-pads
    prompts to power-of-two buckets and keys prefixes on the padded
    stream, so the shared requests all have one length, drawn as
    8 mod 16: the common prefix (padding + system prompt) then ends in
    the middle of a page."""
    system = rng.integers(0, vocab, SYSTEM_LEN)
    shared_len = 16 * int(rng.integers(SYSTEM_LEN // 16 + 1,
                                       MAX_PROMPT // 16)) + 8
    traffic = []
    for c in range(N_SESSIONS):
        prompts = []
        for j in range(PER_SESSION):
            if (c + j) % 2 == 0:
                user = rng.integers(0, vocab, shared_len - SYSTEM_LEN)
                prompts.append(np.concatenate([system, user]))
            else:
                n = int(rng.integers(MIN_PROMPT, MAX_PROMPT + 1))
                prompts.append(rng.integers(0, vocab, n))
        traffic.append(prompts)
    return traffic


def serve(eng, traffic: list, timeout_s: float = 600.0) -> list:
    """Drive the engine with one client thread per session; returns one
    ``(handle, streamed tokens, cancel won)`` per request."""
    results = [[] for _ in traffic]
    errors = []

    def client(c: int) -> None:
        try:
            with eng.connect(c) as session:
                for j, prompt in enumerate(traffic[c]):
                    h = session.submit_i(prompt, max_tokens=MAX_TOKENS,
                                         eos_id=-1)
                    streamed, won = [], None
                    for _, tok in h.tokens(timeout_s=timeout_s):
                        streamed.append(tok)
                        if (c, j) == CANCEL and won is None:
                            won = h.cancel()        # mid-decode
                    results[c].append((h, streamed, won))
        except Exception as exc:    # noqa: BLE001 — reported as a failure
            errors.append(f"session {c}: {exc!r}")

    # Daemon threads: a client stuck past its timeout must not keep the
    # process (and the chip) alive after the run has failed.
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(len(traffic))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout_s)
    if any(t.is_alive() for t in threads):
        errors.append("a client thread did not finish")
    if errors:
        raise SmokeFailure("; ".join(errors))
    return [r for rs in results for r in rs]


def check_requests(results: list, eng) -> list:
    """The serving checks; returns ``(stream, tokens)`` for each
    completed request, where ``stream`` is the bucketed, left-padded
    prompt the engine prefilled."""
    from repro.core import states
    from repro.launch.serve import serve_failure

    failure = serve_failure(eng)
    if failure is None and eng.stats["tick_faults"]:
        failure = (f"{eng.stats['tick_faults']} tick faults caught; "
                   f"first: {eng.first_fault}")
    if failure is not None:
        raise SmokeFailure(failure)
    completed = []
    for h, streamed, won in results:
        r = h.response
        if r is None:
            raise SmokeFailure(f"request {h.req_id} has no terminal")
        if won is not None:
            if not won or r.fsm.state != states.REQUEST_CANCELLED:
                raise SmokeFailure(f"cancel of request {r.req_id} lost: "
                                   f"{r.fsm.state}")
            if len(r.tokens_out) >= MAX_TOKENS:
                raise SmokeFailure("the cancelled request ran to its end")
            continue
        if r.fsm.state != states.REQUEST_COMPLETED or h.status is not None:
            raise SmokeFailure(f"request {r.req_id} ended {r.fsm.state} "
                               f"({h.status})")
        out = [int(t) for t in r.tokens_out]
        if len(out) != MAX_TOKENS or streamed != out:
            raise SmokeFailure(
                f"request {r.req_id}: {len(out)} tokens, "
                f"{len(streamed)} streamed, want {MAX_TOKENS}")
        completed.append((eng._padded_prompt(r), out))
    return completed


def check_pool(eng) -> None:
    """After stop no sequence holds a page: the pool's used pages are
    exactly the pages the prefix cache keeps resident."""
    pool, cache = eng.pool, eng.prefix_cache
    resident = cache.resident_pages()
    if (pool.n_seqs() or pool.quarantined
            or pool.used_pages() != len(resident)
            or pool.free_pages() != pool.n_pages - len(resident)):
        raise SmokeFailure(
            f"pool ledger: {pool.n_seqs()} seqs, {pool.used_pages()} used, "
            f"{pool.free_pages()} free of {pool.n_pages}, "
            f"{len(resident)} held by the prefix cache")


def reference_check(model, params, completed: list, t_ref: int) -> dict:
    """Score every engine token against a cache-free forward pass over
    the stream it was decoded from (padded prompt + its own tokens),
    at one fixed length ``t_ref`` so the reference compiles once."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import unembed_matrix
    from repro.models.losses import full_logits

    cfg = model.cfg

    @jax.jit
    def rows(params, tokens, first):
        hidden, _, _ = model.forward(params, tokens)
        h = jax.lax.dynamic_slice_in_dim(hidden, first, MAX_TOKENS, axis=1)
        w_out = unembed_matrix(params["embed"], cfg).astype(cfg.compute_dtype)
        return full_logits(h, w_out)[0]

    agree = total = 0
    worst = np.inf          # smallest (tolerance - shortfall) seen
    tols = []
    for stream, out in completed:
        seq = np.zeros((1, t_ref), np.int32)
        seq[0, :len(stream)] = stream
        seq[0, len(stream):len(stream) + MAX_TOKENS] = out
        logits = np.asarray(rows(params, jnp.asarray(seq),
                                 len(stream) - 1))          # [MAX_TOKENS, V]
        top = logits.max(axis=1)
        tol = TOL_FRACTION * (top - logits.min(axis=1))
        got = logits[np.arange(MAX_TOKENS), out]
        worst = min(worst, float((tol - (top - got)).min()))
        tols.extend(tol.tolist())
        agree += int((logits.argmax(axis=1) == np.asarray(out)).sum())
        total += MAX_TOKENS
    rate = agree / total
    stats = {"agreement": rate, "tokens": total,
             "tol_median": float(np.median(tols)), "worst_slack": worst}
    if worst < 0:
        raise SmokeFailure(f"a token lies outside the tolerance: {stats}")
    if rate < MIN_AGREEMENT:
        raise SmokeFailure(f"argmax agreement {rate:.4f} < {MIN_AGREEMENT}:"
                           f" {stats}")
    return stats


def run(seed: int, argv: list) -> None:
    """Build, warm up, serve, check; prints observations as it goes."""
    import jax

    from repro.launch import serve as launcher

    t = time.monotonic()
    eng, _, _ = launcher.build_engine(
        launcher.build_parser().parse_args(argv), seed=seed)
    jax.block_until_ready(eng.params)
    print(f"engine: {eng.model.cfg.name} {eng.scheduler}, "
          f"{eng.pool.n_pages} pages x {eng.pool.page_size}, "
          f"built in {time.monotonic() - t:.3f} s")
    t = time.monotonic()
    n_programs = eng.warmup()
    print(f"compile + warm-up: {n_programs} programs in "
          f"{time.monotonic() - t:.3f} s")

    traffic = make_traffic(np.random.default_rng(seed),
                           eng.model.cfg.vocab_size)
    thread = eng.start()
    t = time.monotonic()
    try:
        results = serve(eng, traffic)
    finally:
        eng.stop()
        thread.join(timeout=60)
    wall = time.monotonic() - t
    if thread.is_alive():
        raise SmokeFailure("the engine thread did not stop")
    completed = check_requests(results, eng)
    check_pool(eng)
    pstats = eng.pool.stats()
    if not eng.stats["prefix_hits"] or not pstats["cow_copy_bytes"]:
        raise SmokeFailure(f"no prefix hit or no copy-on-write: "
                           f"{eng.stats['prefix_hits']} hits, "
                           f"{pstats['cow_copy_bytes']} CoW bytes")
    n_tok = sum(len(h.response.tokens_out) for h, _, _ in results)
    print(f"serve: {len(results)} requests ({len(completed)} completed, "
          f"{len(results) - len(completed)} cancelled), {n_tok} tokens "
          f"in {wall:.3f} s wall")
    print(f"prefix cache: {eng.stats['prefix_hits']} hits, "
          f"{eng.stats['prefill_tokens_saved']} prefill tokens saved, "
          f"{pstats['cow_copy_bytes']} CoW bytes")

    t = time.monotonic()
    ref = reference_check(eng.model, eng.params, completed,
                          t_ref=eng._bucket(MAX_PROMPT) + MAX_TOKENS)
    print(f"reference: argmax agreement {ref['agreement']:.4f} over "
          f"{ref['tokens']} tokens; tolerance {TOL_FRACTION:.0%} of each "
          f"row's logit range (median {ref['tol_median']:.4f}), "
          f"smallest slack {ref['worst_slack']:.4f}; "
          f"{time.monotonic() - t:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device_kind: {dev.device_kind} (x{len(jax.devices())})")
    cached = Path(cache_dir)
    print(f"compile cache: {cache_dir} "
          f"({len(list(cached.iterdir())) if cached.is_dir() else 0} "
          f"entries before this run)")
    try:
        run(args.seed, launcher_argv())
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr)
        return 1
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
