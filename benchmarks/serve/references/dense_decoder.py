"""Plain reference of a dense decoder-only transformer, and its weights.

The family of every configuration whose file says
``"family": "dense_decoder"``: pre-norm blocks of grouped-query
attention with rotary positions and a SwiGLU MLP, RMSNorm, and tied or
untied embeddings.  Written from the published description in plain
``jax.numpy``, one sequence at a time, in float32 with
``precision="highest"``; it imports nothing of the program under test.

It follows the program where a configuration lists a ``departures``
entry (the program's semantics are what the benchmark checks); each
departure is named in the configuration file.

Weights: :func:`init_params` makes them from a key, in one jitted call,
in the dtype they are served in, laid out as the program's parameter
tree (``embed``/``layers``/``final_norm``, layers stacked on axis 0).

``mode="fp8"`` is the control: every matrix product takes its inputs
rounded to float8 e4m3 with one scale per tensor, the precision step
below the served bfloat16.  It exists to show that the comparison that
decides ``correct`` fails such a program.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)
Q_BLOCK = 512               # query rows per attention block


def dims(conf: Dict) -> Dict[str, int]:
    d = conf["hidden_size"]
    nh = conf["num_attention_heads"]
    return {"d": d, "nh": nh, "nkv": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or d // nh,
            "f": conf["intermediate_size"], "L": conf["num_hidden_layers"],
            "V": conf["vocab_size"]}


def norm_eps(conf: Dict) -> float:
    return float(conf.get("rms_norm_eps", conf.get("layer_norm_eps")))


def check_supported(conf: Dict) -> None:
    """Refuse a configuration this reference does not compute."""
    if conf.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("rotary positions over part of a head are not "
                         "implemented")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"activation {conf['hidden_act']!r}")
    if conf.get("attention_bias") or conf.get("use_qkv_bias"):
        raise ValueError("attention biases are not implemented")


def program_config(conf: Dict) -> Dict:
    """The program's ``ModelConfig`` fields for this configuration."""
    check_supported(conf)
    k = dims(conf)
    return {"family": "dense", "num_layers": k["L"], "d_model": k["d"],
            "num_heads": k["nh"], "num_kv_heads": k["nkv"], "d_ff": k["f"],
            "vocab_size": k["V"], "head_dim": k["hd"],
            "tie_embeddings": bool(conf["tie_word_embeddings"]),
            "norm_eps": norm_eps(conf),
            "rope_theta": float(conf["rope_theta"]),
            "dtype": conf["serving"]["dtype"]}


def param_shapes(conf: Dict) -> Dict:
    k = dims(conf)
    d, nh, nkv, hd, f, L, V = (k[n] for n in ("d", "nh", "nkv", "hd", "f",
                                              "L", "V"))
    embed = {"table": (V, d)}
    if not conf["tie_word_embeddings"]:
        embed["unembed"] = (d, V)
    return {"embed": embed,
            "layers": {"ln1": (L, d), "ln2": (L, d),
                       "attn": {"wq": (L, d, nh, hd), "wk": (L, d, nkv, hd),
                                "wv": (L, d, nkv, hd), "wo": (L, nh, hd, d)},
                       "mlp": {"wi_gate": (L, d, f), "wi_up": (L, d, f),
                               "wo": (L, f, d)}},
            "final_norm": (d,)}


def _scale(path: str, conf: Dict) -> float:
    """Standard deviation of each leaf: fan-in scaled projections, unit
    embedding rows (so tied logits spread widely, as trained ones do)."""
    k = dims(conf)
    return {"table": 1.0, "unembed": k["d"] ** -0.5,
            "wq": k["d"] ** -0.5, "wk": k["d"] ** -0.5, "wv": k["d"] ** -0.5,
            "attn/wo": (k["nh"] * k["hd"]) ** -0.5,
            "wi_gate": k["d"] ** -0.5, "wi_up": k["d"] ** -0.5,
            "mlp/wo": k["f"] ** -0.5}[path]


def init_params(conf: Dict, key: jax.Array) -> Dict:
    """Random weights from ``key`` in the served dtype.  Norm scales are
    ``1 + 0.1 N(0, 1)`` so a program that skips one is caught."""
    dtype = jnp.dtype(conf["serving"]["dtype"])
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(conf), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        names = [p.key for p in path]
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if names[-1] in ("ln1", "ln2", "final_norm"):
            leaves.append((1.0 + 0.1 * z).astype(dtype))
            continue
        name = names[-1] if names[-1] != "wo" else f"{names[-2]}/wo"
        leaves.append((z * _scale(name, conf)).astype(dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


def make_params(conf: Dict, seed: int) -> Dict:
    """:func:`init_params` on the default device in one jitted call.
    The key takes all 64 bits of the seed."""
    seed = int(seed) & (2**64 - 1)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.jit(functools.partial(init_params, conf))(key)


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------
def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = FP8_MAX / amax
    return (x * s).astype(FP8).astype(jnp.float32) / s


def _mm(spec, a, b, mode):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotary positions over the whole head: the two halves of each
    head rotate as (x1, x2) -> (x1 c - x2 s, x2 c + x1 s)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq               # [T, half]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(x, lp, *, nkv, eps, theta, mode):
    """One block over one sequence ``x`` [T, d] (float32)."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, lp["ln1"], eps)
    q = _rope(_mm("td,dhk->thk", h, lp["attn"]["wq"], mode), pos, theta)
    k = _rope(_mm("td,dhk->thk", h, lp["attn"]["wk"], mode), pos, theta)
    v = _mm("td,dhk->thk", h, lp["attn"]["wv"], mode)
    nh, hd = q.shape[1], q.shape[2]
    group = nh // nkv
    k = jnp.repeat(k, group, axis=1)                          # [T, nh, hd]
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for s0 in range(0, T, Q_BLOCK):                          # causal, blocked
        qb = q[s0:s0 + Q_BLOCK]
        sc = _mm("qhk,shk->hqs", qb, k, mode) * hd ** -0.5
        mask = pos[None, s0:s0 + qb.shape[0], None] >= pos[None, None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(_mm("hqs,shk->qhk", p, v, mode))
    att = jnp.concatenate(outs, 0)
    x = x + _mm("thk,hkd->td", att, lp["attn"]["wo"], mode)
    h = _rms(x, lp["ln2"], eps)
    g = _mm("td,df->tf", h, lp["mlp"]["wi_gate"], mode)
    u = _mm("td,df->tf", h, lp["mlp"]["wi_up"], mode)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, lp["mlp"]["wo"], mode)


@functools.partial(jax.jit, static_argnames=("nkv", "eps", "theta", "mode"))
def _stack(x, layers, *, nkv, eps, theta, mode):
    """Every block in turn, one program per sequence length."""
    def step(x, lp):
        return _layer(x, lp, nkv=nkv, eps=eps, theta=theta, mode=mode), None

    return jax.lax.scan(step, x, layers)[0]


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, rows, final_norm, w_out, *, eps, mode):
    h = _rms(x[rows], final_norm.astype(jnp.float32), eps)
    return _mm("nd,dv->nv", h, w_out.astype(jnp.float32), mode)


def logits_at(conf: Dict, params: Dict, tokens: np.ndarray,
              rows: np.ndarray, mode: str = "f32") -> np.ndarray:
    """Logits [len(rows), V] (float32, on the host) of the token after
    each position in ``rows`` of the sequence ``tokens``.

    The sequence is zero-padded at its end to a multiple of
    ``Q_BLOCK`` (causal attention: later positions change nothing
    before them) and ``rows`` to a multiple of 256, so that a run
    compiles few shapes."""
    k = dims(conf)
    T = -(-len(tokens) // Q_BLOCK) * Q_BLOCK
    seq = np.zeros((T,), np.int32)
    seq[:len(tokens)] = tokens
    n = len(rows)
    rows_p = np.zeros((-(-n // 256) * 256,), np.int32)
    rows_p[:n] = rows
    emb = params["embed"]
    x = jnp.take(emb["table"], jnp.asarray(seq), axis=0).astype(jnp.float32)
    eps, theta = norm_eps(conf), float(conf["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = _stack(x, params["layers"], nkv=k["nkv"], eps=eps, theta=theta,
                   mode=mode)
        w_out = emb["table"].T if conf["tie_word_embeddings"] else emb["unembed"]
        out = _head(x, jnp.asarray(rows_p), params["final_norm"], w_out,
                    eps=eps, mode=mode)
    return np.asarray(out)[:n]
