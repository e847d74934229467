"""Mixture-of-experts FFN with sort-based dispatch and an explicit
expert-parallel shard_map region.

Why shard_map: under plain pjit the data-dependent dispatch scatter defeats
the SPMD partitioner — it materializes *global* (E, capacity, d) buffers
(tens of GB at 1M tokens).  Here the routing/bucketing runs on each shard's
local tokens only:

* experts divisible by the model axis → expert weights shard over "model",
  tokens shard over ("pod","data") and stay replicated across "model";
  each model-rank serves its expert slice for its data-shard's tokens and a
  psum over "model" combines per-token outputs (the EP collective visible
  in the dry-run HLO).
* experts NOT divisible (granite's 40 on a 16-way axis) → expert weights
  replicate, tokens shard over the whole mesh, no combine collective.

Tokens beyond an expert's local capacity are dropped (Switch/GShard
semantics; the aux loss keeps drops rare).  Shared (always-on) experts are
ordinary dense FFN handled by pjit outside the region.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import common


class MoEConfig(NamedTuple):
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01


def moe_params(pf, prefix: str, d_model: int, cfg: MoEConfig):
    e, f = cfg.n_experts, cfg.d_ff_expert
    p = {
        "router": pf.dense(f"{prefix}/router", (d_model, e), (None, None),
                           scale=0.02),
        "w_gate": pf.dense(f"{prefix}/w_gate", (e, d_model, f),
                           ("experts", "embed", "ffn")),
        "w_up": pf.dense(f"{prefix}/w_up", (e, d_model, f),
                         ("experts", "embed", "ffn")),
        "w_down": pf.dense(f"{prefix}/w_down", (e, f, d_model),
                           ("experts", "ffn", "embed")),
    }
    if cfg.n_shared:
        fs = f * cfg.n_shared
        p["shared_gate"] = pf.dense(f"{prefix}/shared_gate", (d_model, fs),
                                    ("embed", "ffn"))
        p["shared_up"] = pf.dense(f"{prefix}/shared_up", (d_model, fs),
                                  ("embed", "ffn"))
        p["shared_down"] = pf.dense(f"{prefix}/shared_down", (fs, d_model),
                                    ("ffn", "embed"))
    return p


def _dispatch_compute(router, w_gate, w_up, w_down, x, cfg: MoEConfig,
                      e_offset, e_local: int, cap: int):
    """Route local tokens, bucket into (e_local, cap, d), compute, combine.

    Returns (y (t, d) — zeros for tokens served by other shards, aux)."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k

    logits = (x @ router).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, tope = jax.lax.top_k(gates, k)
    topv = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(tope, e, dtype=jnp.float32),
                          axis=1), axis=0) / k
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)

    e_f = tope.reshape(-1)
    t_f = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    w_f = topv.reshape(-1).astype(x.dtype)
    local = e_f - e_offset
    mine = (local >= 0) & (local < e_local)
    local = jnp.where(mine, local, e_local)            # ghost bucket

    order = jnp.argsort(local)
    l_s, t_s, w_s = local[order], t_f[order], w_f[order]
    counts = jnp.zeros((e_local + 1,), jnp.int32).at[l_s].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[l_s]
    fits = (pos < cap) & (l_s < e_local)
    slot = jnp.where(fits, l_s * cap + pos, e_local * cap)

    xe = jnp.zeros((e_local * cap + 1, d), x.dtype).at[slot].set(x[t_s])
    xe = xe[:-1].reshape(e_local, cap, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)

    y_flat = jnp.concatenate([ye.reshape(e_local * cap, d),
                              jnp.zeros((1, d), x.dtype)], axis=0)
    contrib = y_flat[slot] * w_s[:, None]
    y = jnp.zeros((t, d), x.dtype).at[t_s].add(
        jnp.where(fits[:, None], contrib, 0))
    return y, aux


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.shape.values())) \
        if hasattr(mesh.shape, "values") else dict(mesh.shape)


def moe_forward(p, x, cfg: MoEConfig, rules=None):
    """x: (T, d_model) -> (T, d_model), plus router aux loss."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    mesh = common.get_abstract_mesh_or_none()

    def shared_part(y):
        if cfg.n_shared:
            y = y + (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])
                     ) @ p["shared_down"]
        return y

    if mesh is None:
        cap = max(int(t * k / e * cfg.capacity_factor), 4)
        y, aux = _dispatch_compute(p["router"], p["w_gate"], p["w_up"],
                                   p["w_down"], x, cfg, 0, e, cap)
        return shared_part(y), aux

    sizes = dict(mesh.shape)
    model_ways = sizes.get("model", 1)
    ep = e % model_ways == 0 and model_ways > 1
    tok_axes = tuple(a for a in ("pod", "data") if a in sizes)
    if not ep and "model" in sizes:
        tok_axes = tok_axes + ("model",)
    # drop trailing axes until the token count divides evenly
    while tok_axes and t % math.prod(sizes[a] for a in tok_axes) != 0:
        tok_axes = tok_axes[:-1]
    tok_ways = math.prod(sizes[a] for a in tok_axes) if tok_axes else 1
    t_local = t // tok_ways
    e_local = e // model_ways if ep else e
    cap = max(int(t_local * k / e * cfg.capacity_factor), 4)

    xspec = P(tok_axes if tok_axes else None, None)
    wspec = P("model", None, None) if ep else P(None, None, None)

    def local_fn(router, w_gate, w_up, w_down, x_local):
        e_off = jax.lax.axis_index("model") * e_local if ep else 0
        y, aux = _dispatch_compute(router, w_gate, w_up, w_down, x_local,
                                   cfg, e_off, e_local, cap)
        if ep:
            y = jax.lax.psum(y, "model")
        if tok_axes:
            aux = jax.lax.pmean(aux, tok_axes)
        return y, aux

    # explicit reshard into the region's token layout — without this, SPMD
    # crosses from the (e.g. 256-way FSDP) layout to the EP layout inside
    # shard_map via involuntary full replication (tens of GB at 1M tokens)
    x = jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, xspec))
    y, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None), wspec, wspec,
                  P("model", None, None) if ep else P(None, None, None),
                  xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return shared_part(y), aux
