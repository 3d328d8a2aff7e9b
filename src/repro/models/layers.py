"""Shared transformer layers: norms, RoPE, GQA attention, SwiGLU MLP.

All layers are pure functions over explicit param dicts.  Parameters are
created through a :class:`ParamBuilder` callback so the same builder code
yields (a) randomly-initialized arrays, (b) logical-axes metadata for
sharding, or (c) abstract shapes for the dry-run — one source of truth.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.parallel.sharding import Axes, shard


# ---------------------------------------------------------------------------
# Parameter builder
# ---------------------------------------------------------------------------
class ParamBuilder:
    """make(name, shape, axes, fan_in=None) -> array | Axes.

    mode="init": fan-in scaled normal init, keyed by a stable hash of the
    parameter path so layer stacking via vmap stays reproducible.
    mode="axes": returns the Axes metadata leaf (for sharding specs).
    """

    def __init__(self, mode: str, rng: Optional[jax.Array] = None,
                 dtype=jnp.bfloat16, prefix: str = ""):
        assert mode in ("init", "axes")
        self.mode = mode
        self.rng = rng
        self.dtype = dtype
        self.prefix = prefix

    def scope(self, name: str) -> "ParamBuilder":
        return ParamBuilder(self.mode, self.rng, self.dtype,
                            self.prefix + name + "/")

    def __call__(self, name: str, shape: Tuple[int, ...], axes: Axes,
                 fan_in: Optional[int] = None, zero: bool = False,
                 scale: Optional[float] = None):
        assert len(shape) == len(axes.names), (self.prefix + name, shape, axes)
        if self.mode == "axes":
            return axes
        path = self.prefix + name
        if zero:
            return jnp.zeros(shape, self.dtype)
        key = jax.random.fold_in(self.rng, zlib.crc32(path.encode()))
        if scale is None:
            fi = fan_in if fan_in is not None else (shape[0] if shape else 1)
            scale = fi ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(self.dtype)


def ones_param(make: ParamBuilder, name: str, dim: int) -> Any:
    if make.mode == "axes":
        return Axes("embed")
    return jnp.ones((dim,), make.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq] (broadcastable)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angle = positions[..., :, None].astype(jnp.float32) * freq  # [..., seq, half]
    cos = jnp.cos(angle)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angle)[..., :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA self / cross, optional sliding window, optional KV cache)
# ---------------------------------------------------------------------------
def attention_params(make: ParamBuilder, cfg: ModelConfig,
                     cross: bool = False) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim_
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    m = make.scope("cross_attn" if cross else "attn")
    p = {
        "wq": m("wq", (d, nh, hd), Axes("embed", "heads", "head_dim"), fan_in=d),
        "wk": m("wk", (d, nkv, hd), Axes("embed", "kv_heads", "head_dim"), fan_in=d),
        "wv": m("wv", (d, nkv, hd), Axes("embed", "kv_heads", "head_dim"), fan_in=d),
        "wo": m("wo", (nh, hd, d), Axes("heads", "head_dim", "embed"), fan_in=nh * hd),
    }
    if cfg.qk_norm:
        if make.mode == "init":
            p["q_norm"] = jnp.ones((hd,), make.dtype)
            p["k_norm"] = jnp.ones((hd,), make.dtype)
        else:
            p["q_norm"] = Axes("head_dim")
            p["k_norm"] = Axes("head_dim")
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, window: int = 0,
                  dtype=None):
    """Ring-buffer KV cache for one layer.  A sliding-window cache *is* a
    circular buffer indexed by position mod window — the NBB slot-rotation
    idea applied to attention state (DESIGN.md §2)."""
    size = min(window, max_len) if window else max_len
    dtype = dtype or cfg.compute_dtype
    kv = (batch, size, cfg.num_kv_heads, cfg.head_dim_)
    return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}


def _shard_cache(c):
    return {
        "k": shard(c["k"], "batch", "cache_seq", "cache_kv_heads", "head_dim"),
        "v": shard(c["v"], "batch", "cache_seq", "cache_kv_heads", "head_dim"),
    }


def is_paged(cache) -> bool:
    """A *paged* cache view (DESIGN.md §10): the KV pool's page arrays
    plus a per-row block table, instead of a dense per-slot ring.  The
    leaves ride the same pytree plumbing as a dense cache, so
    ``decode_loop``/``chunked_block`` run unmodified over either
    backend."""
    return isinstance(cache, dict) and "pages_k" in cache


def _write_rows(pool: jax.Array, li, page: jax.Array, row: jax.Array,
                new: jax.Array, src: jax.Array, ok: jax.Array) -> jax.Array:
    """Write whole rows of ``pool`` [L, n_pages, rows, W] at layer ``li``.

    ``page``/``row`` [B, R] address the rows; lane group ``u`` of row
    ``r`` takes ``new[b, src[b, r, u]]`` ([B, T, F] tokens) where
    ``ok[b, r, u]``, and keeps the pool's value elsewhere.  A page out of
    range drops its row.  Whole rows, because the TPU scatters a window
    that spans the minor axis natively, and one that starts inside it
    as a loop of one update at a time."""
    B, R, g = src.shape
    old = pool[li, jnp.minimum(page, pool.shape[1] - 1), row]
    upd = jnp.take_along_axis(new, src.reshape(B, R * g, 1), axis=1)
    rows = jnp.where(ok[..., None], upd.reshape(B, R, g, -1),
                     old.reshape(B, R, g, -1)).reshape(old.shape)
    return pool.at[li, page, row].set(rows, mode="drop")


def attention(p: Dict[str, Any], cfg: ModelConfig, x: jax.Array,
              positions: jax.Array,
              window: int = 0,
              cache: Optional[Dict[str, jax.Array]] = None,
              cache_pos: Optional[jax.Array] = None,
              kv_source: Optional[jax.Array] = None,
              write_mask: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """GQA attention.

    x: [B, T, D]; positions: [B, T] absolute positions of x tokens.
    window: sliding-window size (0 = global causal).
    cache/cache_pos: decode-mode ring cache and the write position —
        a scalar (whole batch in lockstep, wave scheduling) or a [B]
        vector (per-row positions, the slot-swap continuous batcher:
        each decode slot advances independently, DESIGN.md §4).
    kv_source: cross-attention source [B, S, D] (no causal mask, no rope).
    write_mask: [B, T] bool, per-token cache-write validity (chunked
        zero-copy admission, DESIGN.md §9): positions where the mask is
        False keep the cache's old value, so a fixed-shape prompt chunk
        can be written in place into only the admitting rows of the
        batch cache.  Requires a [B]-vector cache_pos.

    Returns (out [B,T,D], updated cache or None).
    """
    B, T, D = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    cross = kv_source is not None

    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    kv_in = kv_source if cross else x
    k = jnp.einsum("bsd,dhk->bshk", kv_in, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_in, p["wv"])

    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)

    if not cross:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    q = shard(q, "batch", "seq", "heads", "head_dim")

    new_cache = None
    if cache is not None and is_paged(cache):
        # Paged KV residency (DESIGN.md §10): the pool's page arrays ARE
        # the cache; this row's history is addressed through its block
        # table.  Writes scatter the T new tokens to (page, offset)
        # computed on device; reads gather the pages back into position
        # order so the shared ``attend`` below sees exactly the dense
        # layout — token sequences stay byte-identical to the dense
        # backend.  Pages are position-ordered, so a slot's kv position
        # IS its gather index: no ring arithmetic, no wrap epoch.
        # The pool is [L, n_pages, rows, W] (``kv_cache.pool_shape``): a
        # row packs g = W // (nkv*hd) tokens, token t of a page at row
        # t // g, lanes (t % g) * nkv*hd onward.
        kp, vp = cache["pages_k"], cache["pages_v"]
        li, blockt = cache["layer"], cache["block"]
        n_pages, rows, width = kp.shape[1:]
        g = width // (nkv * hd)
        ps = rows * g
        cp = jnp.asarray(cache_pos)
        if cp.ndim == 0:
            raise ValueError("paged cache requires a [B] vector cache_pos "
                             "(per-row block tables)")
        # The rows the T new tokens touch, in sequence order: row r holds
        # positions r*g .. r*g+g-1; lane group u of it is chunk token t.
        r = cp[:, None] // g + jnp.arange((T + 2 * g - 2) // g)   # [B, R]
        page = jnp.take_along_axis(blockt, r // rows, axis=1, mode="clip")
        t = r[:, :, None] * g + jnp.arange(g) - cp[:, None, None]
        src = jnp.clip(t, 0, T - 1)                         # [B, R, g]
        ok = (t >= 0) & (t < T)
        if write_mask is not None:
            # Rows not writing this dispatch (idle/dead decode rows, the
            # padded tail of a final chunk) must DROP their writes: in
            # the shared pool a masked row's junk write could land in a
            # page another sequence owns — unlike the dense cache, where
            # each row's junk stays in its own private rows.
            ok &= jnp.take_along_axis(write_mask, src.reshape(B, -1),
                                      axis=1).reshape(src.shape)
            total = cp[:, None] + jnp.sum(write_mask, axis=1,
                                          keepdims=True)   # [B, 1]
        else:
            total = cp[:, None] + T
        # A row with nothing to write gets an out-of-range page: the
        # scatter drops it (the write-enable).
        page = jnp.where(ok.any(-1), page, n_pages)
        k_new = k.astype(kp.dtype).reshape(B, T, nkv * hd)
        v_new = v.astype(vp.dtype).reshape(B, T, nkv * hd)
        kp = _write_rows(kp, li, page, r % rows, k_new, src, ok)
        vp = _write_rows(vp, li, page, r % rows, v_new, src, ok)
        new_cache = {"pages_k": kp, "pages_v": vp, "block": blockt,
                     "layer": li}
        S = blockt.shape[1] * ps
        # Gather ONLY this batch's pages at this layer ([B, P, rows, W]
        # — O(B*max_len), not O(n_pages)): the jnp expression of the
        # Pallas kernel's per-page index_map DMA.
        k = kp[li, blockt].reshape(B, S, nkv, hd)
        v = vp[li, blockt].reshape(B, S, nkv, hd)
        kv_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        kv_valid = jnp.arange(S)[None, :] < total           # [B, S]
    elif cache is not None:
        size = cache["k"].shape[1]
        cp = jnp.asarray(cache_pos)
        if cp.ndim == 0:
            if write_mask is not None:
                raise ValueError("write_mask requires a [B] vector "
                                 "cache_pos (per-row chunked admission)")
            # Lockstep decode: write k/v of the T new tokens into the
            # same ring slots for every batch row.
            slots = (cp + jnp.arange(T)) % size             # [T]
            k_full = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
            v_full = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
            total = cp + T                                  # tokens so far
            slot_ids = jnp.arange(size)
            valid = slot_ids < jnp.minimum(total, size)     # [S]
            # Absolute position held by each slot (causal/window masking).
            wraps = (total - 1) // size
            slot_pos = jnp.where(
                slot_ids <= (total - 1) % size,
                wraps * size + slot_ids,
                jnp.maximum(wraps - 1, 0) * size + slot_ids,
            )                                               # [S]
            kv_pos = jnp.broadcast_to(slot_pos, (B, size))
            kv_valid = jnp.broadcast_to(valid, (B, size))
        else:
            # Per-row decode (slot-swap continuous batching): every batch
            # row is an independent sequence at its own position; rows
            # whose slot is idle write to slot 0 but are masked out by
            # their own row's validity, never by neighbours'.
            slots = (cp[:, None] + jnp.arange(T)) % size    # [B, T]
            b_idx = jnp.arange(B)[:, None]
            k_new = k.astype(cache["k"].dtype)
            v_new = v.astype(cache["v"].dtype)
            if write_mask is not None:
                # Chunked admission: only slots actually carrying prompt
                # tokens are written; every other (row, slot) keeps its
                # old value via a gather+where on the T touched slots —
                # the full cache is never copied.
                wm = write_mask[:, :, None, None]
                k_new = jnp.where(wm, k_new, cache["k"][b_idx, slots])
                v_new = jnp.where(wm, v_new, cache["v"][b_idx, slots])
            k_full = cache["k"].at[b_idx, slots].set(k_new)
            v_full = cache["v"].at[b_idx, slots].set(v_new)
            if write_mask is not None:
                # The row's true extent is its VALID token count, not T:
                # counting a final chunk's padded tail would (a) mark
                # never-written slots valid and (b) push ``total`` past
                # the ring size, bumping the wrap epoch and mislabeling
                # the oldest slots' positions — causally masking real
                # prompt KV from the chunk's own queries.
                total = cp[:, None] + jnp.sum(write_mask, axis=1,
                                              keepdims=True)  # [B, 1]
            else:
                total = cp[:, None] + T                     # [B, 1]
            slot_ids = jnp.arange(size)[None, :]            # [1, S]
            valid = slot_ids < jnp.minimum(total, size)     # [B, S]
            wraps = (total - 1) // size
            slot_pos = jnp.where(
                slot_ids <= (total - 1) % size,
                wraps * size + slot_ids,
                jnp.maximum(wraps - 1, 0) * size + slot_ids,
            )                                               # [B, S]
            kv_pos, kv_valid = slot_pos, valid
        new_cache = _shard_cache({"k": k_full, "v": v_full})
        k, v = new_cache["k"], new_cache["v"]
    else:
        kv_pos = positions if not cross else None
        kv_valid = None

    k = shard(k, "batch", "cache_seq" if cache is not None else "seq",
              "kv_heads" if cache is None else "cache_kv_heads", "head_dim")
    v = shard(v, "batch", "cache_seq" if cache is not None else "seq",
              "kv_heads" if cache is None else "cache_kv_heads", "head_dim")

    # GQA: fold the group dimension into q.
    group = nh // nkv
    qg = q.reshape(B, T, nkv, group, hd)

    softcap = cfg.attn_logit_softcap

    def attend(q_blk, q_pos_blk):
        """q_blk: [B, t, kv, g, hd]; q_pos_blk: [B, t].  Full-S attention of
        one query block (memory O(t*S), bounded by the chunk loop below)."""
        scores = jnp.einsum("btkgh,bskh->bkgts", q_blk, k,
                            preferred_element_type=jnp.float32) * (hd ** -0.5)
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        if not cross:
            mask = kv_pos[:, None, :] <= q_pos_blk[:, :, None]   # causal
            if window:
                mask &= kv_pos[:, None, :] > q_pos_blk[:, :, None] - window
            if kv_valid is not None:
                mask &= kv_valid[:, None, :]
            scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgts,bskh->btkgh", probs, v)

    # Query-chunked attention for LONG sequences only.  Measured on the
    # dry-run (EXPERIMENTS.md §Perf, "full-length loss" iteration): at
    # T=4k the chunk scan *adds* fusion-boundary HBM traffic (+24-28%
    # memory term) versus one fused attend, while at 32k the unchunked
    # [T,S] f32 scores (4 GB/head) are unshippable — so chunk iff T >= 8k.
    qchunk = 2048
    if T >= 8192 and T % qchunk == 0:
        # Scan over query blocks so the [t, S] score tile is the only
        # transient (the Pallas flash kernel mirrors this blocking
        # on-chip).  The chunk body is rematted: backward recomputes
        # each tile instead of saving T/qchunk of them.
        nq = T // qchunk
        q_blks = qg.reshape(B, nq, qchunk, nkv, group, hd).transpose(
            1, 0, 2, 3, 4, 5)
        p_blks = positions.reshape(B, nq, qchunk).transpose(1, 0, 2)

        @jax.checkpoint
        def body(_, inp):
            qb, pb = inp
            return None, attend(qb, pb)

        _, out = jax.lax.scan(body, None, (q_blks, p_blks))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, nh, hd)
    else:
        out = attend(qg, positions).reshape(B, T, nh, hd)

    out = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return shard(out, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def mlp_params(make: ParamBuilder, cfg: ModelConfig,
               d_ff: Optional[int] = None) -> Dict[str, Any]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    m = make.scope("mlp")
    return {
        "wi_gate": m("wi_gate", (d, f), Axes("embed", "mlp"), fan_in=d),
        "wi_up": m("wi_up", (d, f), Axes("embed", "mlp"), fan_in=d),
        "wo": m("wo", (f, d), Axes("mlp", "embed"), fan_in=f),
    }


def mlp(p: Dict[str, Any], x: jax.Array) -> jax.Array:
    h = jax.nn.silu(jnp.einsum("btd,df->btf", x, p["wi_gate"]))
    h = h * jnp.einsum("btd,df->btf", x, p["wi_up"])
    h = shard(h, "batch", "seq", "mlp")
    out = jnp.einsum("btf,fd->btd", h, p["wo"])
    return shard(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_params(make: ParamBuilder, cfg: ModelConfig) -> Dict[str, Any]:
    m = make.scope("embed")
    p = {"table": m("table", (cfg.vocab_size, cfg.d_model),
                    Axes("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        p["unembed"] = m("unembed", (cfg.d_model, cfg.vocab_size),
                         Axes("embed", "vocab"), fan_in=cfg.d_model)
    return p


def embed(p: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(p["table"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return shard(x, "batch", "seq", "embed")


def unembed_matrix(p: Dict[str, Any], cfg: ModelConfig) -> jax.Array:
    """Returns W_out [d_model, vocab]."""
    if cfg.tie_embeddings:
        return p["table"].T
    return p["unembed"]
