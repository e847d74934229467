"""Shared model-building blocks: init helpers, norms, RoPE, logical sharding.

All models are functional (pure init/apply), pytree-parameterized, and carry
their sharding intent through *logical axis names* resolved against per-run
rules — the standard MaxText-style pattern, implemented minimally:

    dense(..., names=("embed", "ffn"))       # annotate
    rules = {"embed": None, "ffn": "model"}  # resolve per arch × shape
    pspec = resolve_pspec(names, rules)      # -> PartitionSpec

Resolving at jit boundary (in_shardings / with_sharding_constraint) is what
the dry-run exercises on the production meshes.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Pytree = Any

get_abstract_mesh = jax.sharding.get_abstract_mesh
use_abstract_mesh = jax.sharding.use_abstract_mesh

# ---------------------------------------------------------------------------
# logical sharding
# ---------------------------------------------------------------------------

# default rules for a ("data", "model") mesh; "pod" extends data-parallelism
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "qk": None,
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "seq": None,
    "kv_seq": None,
    "rows": "model",       # embedding-table rows (recsys)
    "cols": None,
    "nodes": ("pod", "data", "model"),   # flat GNN sharding
    "edges": ("pod", "data", "model"),
    "candidates": "model",
    "stack": None,         # scan-over-layers leading axis
}


def resolve_pspec(names: tuple, rules: dict, mesh=None) -> P:
    """Map logical axis names to a PartitionSpec under `rules`.

    Axes whose mesh axis is absent from `mesh` (e.g. "pod" on the single-pod
    mesh) are dropped from the spec.
    """
    mesh_axes = set(mesh.axis_names) if mesh is not None else None
    used: set = set()

    def ok(ax):
        return (mesh_axes is None or ax in mesh_axes) and ax not in used

    spec = []
    for n in names:
        r = rules.get(n, None) if n is not None else None
        if r is None:
            spec.append(None)
        elif isinstance(r, tuple):
            kept = tuple(a for a in r if ok(a))
            used.update(kept)
            spec.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            if ok(r):
                used.add(r)
                spec.append(r)
            else:
                spec.append(None)
    return P(*spec)


def tree_pspecs(names_tree: Pytree, rules: dict, mesh=None) -> Pytree:
    return jax.tree.map(lambda names: resolve_pspec(names, rules, mesh),
                        names_tree, is_leaf=lambda x: isinstance(x, tuple))


def fit_spec_to_shape(spec: P, shape: tuple, mesh) -> P:
    """Drop sharding on dims the mesh axes don't divide evenly."""
    sizes = dict(mesh.shape)
    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        axes = (ax,) if isinstance(ax, str) else (ax or ())
        ways = 1
        for a in axes:
            ways *= sizes[a]
        fixed.append(ax if ways > 0 and dim % ways == 0 else None)
    return P(*fixed)


def constrain(x, names: tuple, rules: dict, mesh=None):
    """with_sharding_constraint via logical names (no-op when no mesh is in
    scope, e.g. single-device smoke tests)."""
    m = mesh or get_abstract_mesh_or_none()
    if m is None:
        return x
    spec = fit_spec_to_shape(resolve_pspec(names, rules, m), x.shape, m)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(m, spec))


def get_abstract_mesh_or_none():
    m = get_abstract_mesh()
    return m if m is not None and m and m.axis_names else None


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

class ParamFactory:
    """Creates parameters and records their logical sharding names.

    `abstract=True` produces ShapeDtypeStructs (for .lower()/dry-run) so no
    multi-GB model is ever materialized on the host.
    """

    def __init__(self, rng, abstract: bool = False, dtype=jnp.float32):
        self._rng = rng
        self.abstract = abstract
        self.dtype = dtype
        self.names: dict = {}

    def _next(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def dense(self, path: str, shape: tuple, names: tuple, scale=None):
        assert len(shape) == len(names), (path, shape, names)
        self.names[path] = names
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, self.dtype)
        fan_in = shape[0] if len(shape) >= 1 else 1
        scale = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(self._next(), shape, self.dtype) * scale)

    def zeros(self, path: str, shape: tuple, names: tuple):
        self.names[path] = names
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, self.dtype)
        return jnp.zeros(shape, self.dtype)

    def ones(self, path: str, shape: tuple, names: tuple):
        self.names[path] = names
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, self.dtype)
        return jnp.ones(shape, self.dtype)


def names_tree_of(params: Pytree, names: dict) -> Pytree:
    """Reconstruct a names-tree congruent with `params`.

    Relies on the convention that the `path` string passed to the factory
    equals the '/'-joined nesting keys of the leaf in the returned tree.
    """
    flat, treedef = jax.tree.flatten_with_path(params)
    out = []
    for path, _ in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append(names[key])
    return jax.tree.unflatten(treedef, out)


class _StackedFactory:
    """Wraps a ParamFactory so every leaf gets a leading (n_layers,) axis —
    the layout lax.scan-over-layers consumes."""

    def __init__(self, pf: ParamFactory, n_layers: int):
        self._pf = pf
        self._n = n_layers
        self.abstract = pf.abstract
        self.dtype = pf.dtype

    def dense(self, path, shape, names, scale=None):
        return self._pf.dense(path, (self._n,) + shape, ("stack",) + names,
                              scale)

    def zeros(self, path, shape, names):
        return self._pf.zeros(path, (self._n,) + shape, ("stack",) + names)

    def ones(self, path, shape, names):
        return self._pf.ones(path, (self._n,) + shape, ("stack",) + names)


def stack_layer_params(factory_fn: Callable, pf: ParamFactory,
                       n_layers: int, prefix: str) -> dict:
    """Build per-layer params with a leading stacked axis for lax.scan."""
    return factory_fn(_StackedFactory(pf, n_layers), prefix)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding. x: (..., S, D even); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = x @ w_gate
    u = x @ w_up
    return (jax.nn.silu(g) * u) @ w_down


def gelu_mlp(x, w1, b1, w2, b2):
    return jax.nn.gelu(x @ w1 + b1) @ w2 + b2


def cross_entropy(logits, labels, vocab: int):
    """Mean token cross-entropy, fp32, ignoring labels < 0.

    When the logits dim is padded beyond `vocab` (vocab-axis sharding
    padding), the padded slots are masked out of the partition function."""
    logits = logits.astype(jnp.float32)
    if logits.shape[-1] > vocab:
        pad_mask = jnp.arange(logits.shape[-1]) < vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    loss = lse - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
