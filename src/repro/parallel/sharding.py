"""Logical-axis sharding rules (MaxText-style) for pjit/GSPMD.

Models annotate activations/params with *logical* axis names ("batch",
"embed", "mlp", ...).  A rules table maps logical names to mesh axes; the
table + mesh are installed with :func:`axis_rules` around tracing.  Outside
any rules context (CPU smoke tests) every annotation is a no-op, so the same
model code runs unsharded on one device and sharded on 512.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


# Default rules for the production meshes of DESIGN.md §7.
# "batch" spreads over pod+data; "model"-parallel dims over the model axis.
DEFAULT_RULES: Dict[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "seq": None,                # sequence kept local by default
    "seq_model": "model",       # context-parallel sequence (long ctx / big attn)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "qkv": "model",             # flattened attention projection dim
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert_mlp": None,
    "expert_data": "data",      # FSDP-style extra shard for expert weights
    "cache_seq": "model",       # decode KV cache: shard seq over model
    "cache_kv_heads": None,
    "conv_kernel": None,
    "state": None,
    "layers": None,
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[Dict[str, MeshAxes]] = None


_ctx = _Ctx()


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Dict[str, MeshAxes]] = None):
    """Install (mesh, rules) for `shard()`/`spec_for()` during tracing."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    # Drop references to mesh axes the mesh doesn't have (e.g. "pod" on the
    # single-pod mesh) so one rules table serves both meshes.
    have = set(mesh.axis_names)

    def _filter(v: MeshAxes) -> MeshAxes:
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in have else None
        kept = tuple(a for a in v if a in have)
        return kept if kept else None

    merged = {k: _filter(v) for k, v in merged.items()}
    prev = (_ctx.mesh, _ctx.rules)
    _ctx.mesh, _ctx.rules = mesh, merged
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def active() -> bool:
    return _ctx.mesh is not None


def spec_for(names: Sequence[Optional[str]]) -> P:
    """Logical axis names -> PartitionSpec under the active rules."""
    assert _ctx.rules is not None
    entries = []
    used = set()
    for n in names:
        v = _ctx.rules.get(n) if n is not None else None
        # A mesh axis may appear at most once in a spec; later dims lose.
        if isinstance(v, str):
            v = (v,) if v not in used else None
        elif isinstance(v, tuple):
            v = tuple(a for a in v if a not in used) or None
        if v is not None:
            used.update(v if isinstance(v, tuple) else (v,))
            entries.append(v if len(v) > 1 else v[0])
        else:
            entries.append(None)
    return P(*entries)


def sharding_for(names: Sequence[Optional[str]]) -> NamedSharding:
    return NamedSharding(_ctx.mesh, spec_for(names))


def shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Annotate ``x`` with logical axes; no-op outside a rules context."""
    if not active():
        return x
    if len(names) != x.ndim:
        raise ValueError(f"shard(): {len(names)} names for rank-{x.ndim} array")
    return jax.lax.with_sharding_constraint(x, sharding_for(names))


class Axes:
    """Logical axes metadata for one parameter (a pytree *leaf*)."""

    __slots__ = ("names",)

    def __init__(self, *names: Optional[str]):
        self.names = tuple(names)

    def prepend(self, name: Optional[str]) -> "Axes":
        return Axes(name, *self.names)

    def __repr__(self):
        return f"Axes{self.names}"

    def __eq__(self, other):
        return isinstance(other, Axes) and self.names == other.names

    def __hash__(self):
        return hash(self.names)


def specs_tree(axes_tree):
    """Map a tree of Axes -> tree of PartitionSpec under active rules."""
    return jax.tree.map(
        lambda a: spec_for(a.names),
        axes_tree,
        is_leaf=lambda x: isinstance(x, Axes),
    )


def shardings_tree(axes_tree):
    return jax.tree.map(
        lambda a: sharding_for(a.names),
        axes_tree,
        is_leaf=lambda x: isinstance(x, Axes),
    )
