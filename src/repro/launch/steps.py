"""Step builders: one compiled function per (arch × shape) dry-run cell.

For each cell this module constructs
  * the jit-able step function (train_step / prefill / decode_step / serve),
  * ShapeDtypeStruct stand-ins for every argument (no allocation),
  * NamedShardings resolved from the family × shape logical rules,
so ``dryrun.py`` can do ``jax.jit(fn, in_shardings=...).lower(*args).compile()``
per mesh and read off memory/cost/collective analyses.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.configs.shapes import FAMILY_SHAPES, ShapeCell, extras_dict, rules_for
from repro.models import common, gnn, recsys
from repro.models import transformer as tr
from repro.train import optimizer

SDS = jax.ShapeDtypeStruct


@dataclass
class Cell:
    arch_id: str
    shape_name: str
    family: str
    kind: str
    fn: Callable
    args: tuple
    in_shardings: Any
    out_shardings: Any
    donate_argnums: tuple
    meta: dict = field(default_factory=dict)


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _zero_rules(rules: dict) -> dict:
    """ZeRO-1: optimizer moments additionally shard over the data axis on
    dims the model rules leave unsharded (stack / embed are the big ones).
    Grads reduce-scatter into this layout and updated params all-gather
    back — XLA derives both from the sharding annotations."""
    z = dict(rules)
    z["stack"] = ("data",) if z.get("stack") is None else z["stack"]
    z["embed"] = ("data",) if z.get("embed") is None else z["embed"]
    return z


def _shard_tree(mesh, names_tree, rules, shapes=None):
    """names -> NamedShardings; with `shapes` (a congruent SDS tree), specs
    are fitted per-leaf so non-divisible dims fall back to replication."""
    if shapes is None:
        return jax.tree.map(
            lambda names: _ns(mesh, common.resolve_pspec(names, rules, mesh)),
            names_tree, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.map(
        lambda leaf, names: _ns(mesh, common.fit_spec_to_shape(
            common.resolve_pspec(names, rules, mesh), leaf.shape, mesh)),
        shapes, names_tree,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))


def _batch_spec(mesh, rules, extra_dims=0):
    bspec = common.resolve_pspec(("batch",) + (None,) * extra_dims, rules, mesh)
    return _ns(mesh, bspec)


def _round_up(x, m):
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_cell(arch_id, config, cell: ShapeCell, mesh, rules) -> Cell:
    # divisibility fallbacks: if a raw count doesn't divide the TP degree,
    # drop that logical axis from sharding (flattened weight dims still
    # shard via their own names)
    model_ways = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if config.moe is not None and config.moe.n_experts % model_ways != 0:
        rules["experts"] = None
    if cell.kind in ("decode", "prefill"):
        rules["kv_heads"] = None       # cache kv-head counts (4/8) < TP=16
    params, names = tr.init(config, abstract=True)
    names_tree = common.names_tree_of(params, names)
    p_shard = _shard_tree(mesh, names_tree, rules, params)
    b, s = cell.global_batch, cell.seq_len
    repl = _ns(mesh, P())
    tok_shard = _ns(mesh, common.resolve_pspec(("batch", None), rules, mesh))
    meta = {
        "params": config.param_count(),
        "active_params": config.active_param_count(),
        "tokens_per_step": b * s if cell.kind == "train" else b,
    }

    if cell.kind == "train":
        # per-arch layout pick (§Perf: FSDP default; tpsp where FSDP's
        # vocab/EP buffers exceed HBM)
        if getattr(config, "train_layout", "fsdp") == "tpsp":
            from repro.configs.shapes import LM_TRAIN_TPSP
            rules = dict(LM_TRAIN_TPSP)
        # FSDP batch axes: greedily take mesh axes while the global batch
        # stays divisible (multi-pod: 256 % 512 != 0 → ("pod", "data"))
        if rules.get("batch") == ("pod", "data", "model"):
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            taken, ways = [], 1
            for ax in ("pod", "data", "model"):
                if ax not in sizes:
                    continue
                if b % (ways * sizes[ax]) != 0:
                    break
                taken.append(ax)
                ways *= sizes[ax]
            rules["batch"] = tuple(taken) or None
            if ("model" not in taken and "model" in sizes
                    and s % sizes["model"] == 0):
                # hybrid FSDP+SP: batch alone can't cover the mesh (e.g.
                # 256 seqs on 512 chips) — shard the sequence over "model"
                # so saved activations stay bounded
                rules["seq"] = "model"
        tok_shard = _ns(mesh, common.resolve_pspec(("batch", None), rules,
                                                   mesh))
        opt = optimizer.abstract_init(params)
        zr = _zero_rules(rules)
        opt_shard = optimizer.OptState(
            m=_shard_tree(mesh, names_tree, zr, params),
            v=_shard_tree(mesh, names_tree, zr, params), step=repl)
        ocfg = optimizer.AdamWConfig()

        mb = getattr(config, "train_microbatches", 1)

        def train_step(params, opt, tokens, labels):
            if mb == 1:
                loss, grads = jax.value_and_grad(tr.loss_fn)(
                    params, config, tokens, labels, rules)
            else:
                # grad accumulation: halves activation temps per microbatch;
                # the bucketed psum of microbatch i overlaps compute of i+1
                tk = tokens.reshape(mb, b // mb, s)
                lb = labels.reshape(mb, b // mb, s)

                def acc(carry, sl):
                    l, g = jax.value_and_grad(tr.loss_fn)(
                        params, config, sl[0], sl[1], rules)
                    return (carry[0] + l,
                            jax.tree.map(jnp.add, carry[1], g)), None

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, p.dtype), params)
                (lsum, grads), _ = jax.lax.scan(acc, (0.0, zero), (tk, lb))
                loss = lsum / mb
                grads = jax.tree.map(lambda g: g / mb, grads)
            new_p, new_opt, metrics = optimizer.apply(params, grads, opt, ocfg)
            return new_p, new_opt, loss, metrics

        args = (params, opt,
                SDS((b, s), jnp.int32), SDS((b, s), jnp.int32))
        in_sh = (p_shard, opt_shard, tok_shard, tok_shard)
        out_sh = (p_shard, opt_shard, repl, {"grad_norm": repl, "lr": repl})
        return Cell(arch_id, cell.name, "lm", cell.kind, train_step, args,
                    in_sh, out_sh, donate_argnums=(0, 1), meta=meta)

    if cell.kind == "prefill":
        # cache is the big output: shard its sequence over model
        cache_rules = dict(rules, kv_seq="model")
        _, cache_names = tr.init_cache(config, b, s, abstract=True)
        cache_shard = jax.tree.map(
            lambda n: _ns(mesh, common.resolve_pspec(n, cache_rules, mesh)),
            cache_names, is_leaf=lambda x: isinstance(x, tuple))

        def prefill_step(params, tokens):
            return tr.prefill(params, config, tokens, rules)

        args = (params, SDS((b, s), jnp.int32))
        out_sh = (_ns(mesh, common.resolve_pspec(("batch", "vocab"), rules,
                                                 mesh)), cache_shard)
        return Cell(arch_id, cell.name, "lm", cell.kind, prefill_step, args,
                    (p_shard, tok_shard), out_sh, donate_argnums=(),
                    meta=meta)

    # decode
    cache, cache_names = tr.init_cache(config, b, s, abstract=True)
    batch_shardable = b % _mesh_batch_ways(mesh, rules) == 0 and b > 1
    dec_rules = dict(rules)
    if not batch_shardable:
        dec_rules["batch"] = None
        # batch=1 leaves the data axis idle: shard the KV sequence over
        # BOTH axes (103 GB moonshot cache -> 400 MB/device)
        dec_rules["kv_seq"] = ("data", "model")
    if (config.attention != "mla"
            and config.n_kv_heads % model_ways == 0 and model_ways > 1):
        # kv-head sharding also engages the model axis for the cache
        dec_rules["kv_heads"] = "model"
        dec_rules["kv_seq"] = ("data",) if not batch_shardable else None
    cache_shard = jax.tree.map(
        lambda n: _ns(mesh, common.resolve_pspec(n, dec_rules, mesh)),
        cache_names, is_leaf=lambda x: isinstance(x, tuple))
    tok1 = _ns(mesh, common.resolve_pspec(("batch",), dec_rules, mesh))

    def decode(params, token, cache, kv_len):
        logits, new_cache = tr.decode_step(params, config, token, cache,
                                           kv_len, dec_rules)
        return logits, new_cache

    args = (params, SDS((b,), jnp.int32), cache, SDS((b,), jnp.int32))
    in_sh = (p_shard, tok1, cache_shard, tok1)
    out_sh = (_ns(mesh, common.resolve_pspec(("batch", "vocab"), dec_rules,
                                             mesh)), cache_shard)
    return Cell(arch_id, cell.name, "lm", cell.kind, decode, args, in_sh,
                out_sh, donate_argnums=(2,), meta=meta)


def _mesh_batch_ways(mesh, rules):
    ways = 1
    r = rules.get("batch")
    r = (r,) if isinstance(r, str) else (r or ())
    for ax in r:
        if ax in mesh.axis_names:
            ways *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
    return ways


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

def _gnn_shapes(cell: ShapeCell, n_dev: int):
    ex = extras_dict(cell)
    if cell.name == "minibatch_lg":
        seeds = ex["batch_nodes"]
        f1, f2 = ex["fanouts"]
        e = seeds * f1 + seeds * f1 * f2
        n = seeds + seeds * f1 + seeds * f1 * f2
    elif cell.name == "molecule":
        n = ex["n_nodes"] * ex["batch"]
        e = ex["n_edges"] * ex["batch"]
    else:
        n, e = ex["n_nodes"], ex["n_edges"]
    t = e * ex["trip_factor"]
    pad = max(n_dev, 512)
    return (_round_up(n, pad), _round_up(e, pad), _round_up(t, pad),
            ex["d_feat"])


def _gnn_cell(arch_id, config, cell: ShapeCell, mesh, rules) -> Cell:
    n_dev = int(mesh.devices.size)
    n, e, t, d_feat = _gnn_shapes(cell, n_dev)
    kw = {"d_feat": d_feat}
    if cell.name == "ogb_products":
        kw["dtype"] = "bfloat16"   # halves the 61.8M-edge message tensors
    config = type(config)(**{**config.__dict__, **kw})
    params, names = gnn.init(config, abstract=True)
    names_tree = common.names_tree_of(params, names)
    p_shard = _shard_tree(mesh, names_tree, rules, params)
    repl = _ns(mesh, P())
    flat = _ns(mesh, common.resolve_pspec(("edges",), rules, mesh))
    flat2 = _ns(mesh, common.resolve_pspec(("edges", None), rules, mesh))
    nshard = _ns(mesh, common.resolve_pspec(("nodes",), rules, mesh))
    nshard2 = _ns(mesh, common.resolve_pspec(("nodes", None), rules, mesh))

    batch = {
        "feat": SDS((n, d_feat), jnp.float32),
        "pos": SDS((n, 3), jnp.float32),
        "edge_src": SDS((e,), jnp.int32),
        "edge_dst": SDS((e,), jnp.int32),
        "trip_kj": SDS((t,), jnp.int32),
        "trip_ji": SDS((t,), jnp.int32),
        "edge_mask": SDS((e,), jnp.float32),
        "trip_mask": SDS((t,), jnp.float32),
        "node_mask": SDS((n,), jnp.float32),
        "target": SDS((n,), jnp.float32),
    }
    b_shard = {
        "feat": nshard2, "pos": nshard2, "edge_src": flat, "edge_dst": flat,
        "trip_kj": flat, "trip_ji": flat, "edge_mask": flat,
        "trip_mask": flat, "node_mask": nshard, "target": nshard,
    }
    opt = optimizer.abstract_init(params)
    zr = _zero_rules(rules)
    opt_shard = optimizer.OptState(m=_shard_tree(mesh, names_tree, zr, params),
                                   v=_shard_tree(mesh, names_tree, zr, params),
                                   step=repl)
    ocfg = optimizer.AdamWConfig()

    if rules.get("partition_gnn"):
        # partitioned-graph layout (see gnn.loss_fn_partitioned): edge and
        # triplet arrays are per-shard local slices; one psum per pass
        flat_axes = tuple(a for a in ("pod", "data", "model")
                          if a in mesh.axis_names)
        edge_keys = ("edge_src", "edge_dst", "trip_kj", "trip_ji",
                     "edge_mask", "trip_mask")
        b_specs = {k: (P(flat_axes) if k in edge_keys else P())
                   for k in batch}

        def loss_sharded(params, batch):
            return shard_map(
                lambda p, b_: gnn.loss_fn_partitioned(p, config, b_,
                                                      flat_axes),
                mesh=mesh, in_specs=(P(), b_specs), out_specs=P(),
                check_vma=False)(params, batch)

        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(loss_sharded)(params, batch)
            new_p, new_opt, metrics = optimizer.apply(params, grads, opt,
                                                      ocfg)
            return new_p, new_opt, loss, metrics
    else:
        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(gnn.loss_fn)(params, config,
                                                          batch)
            new_p, new_opt, metrics = optimizer.apply(params, grads, opt,
                                                      ocfg)
            return new_p, new_opt, loss, metrics

    meta = {"n_nodes": n, "n_edges": e, "n_triplets": t,
            "params": sum(int(math.prod(l.shape))
                          for l in jax.tree.leaves(params))}
    return Cell(arch_id, cell.name, "gnn", "train", train_step,
                (params, opt, batch), (p_shard, opt_shard, b_shard),
                (p_shard, opt_shard, repl, {"grad_norm": repl, "lr": repl}),
                donate_argnums=(0, 1), meta=meta)


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

def _recsys_batch(config, cell: ShapeCell, mesh, rules):
    b = cell.global_batch
    c = config
    if c.kind in ("deepfm", "xdeepfm"):
        batch = {"ids": SDS((b, c.n_sparse), jnp.int32),
                 "label": SDS((b,), jnp.int32)}
    elif c.kind == "two_tower":
        batch = {"user_ids": SDS((b, c.n_user_feats), jnp.int32),
                 "user_mask": SDS((b, c.n_user_feats), jnp.float32),
                 "item_ids": SDS((b, c.n_item_feats), jnp.int32),
                 "item_mask": SDS((b, c.n_item_feats), jnp.float32),
                 "log_q": SDS((b,), jnp.float32)}
    else:  # bert4rec
        m, cands = 8, 2048
        batch = {"items": SDS((b, c.seq_len), jnp.int32),
                 "positions": SDS((b, m), jnp.int32),
                 "label_idx": SDS((b, m), jnp.int32),
                 "candidates": SDS((cands,), jnp.int32)}
    shard = {}
    for k, v in batch.items():
        if k == "candidates":
            shard[k] = _ns(mesh, P())
        else:
            shard[k] = _ns(mesh, common.resolve_pspec(
                ("batch",) + (None,) * (len(v.shape) - 1), rules, mesh))
    return batch, shard


def _recsys_cell(arch_id, config, cell: ShapeCell, mesh, rules) -> Cell:
    c = config
    params, names = recsys.init(c, abstract=True)
    names_tree = common.names_tree_of(params, names)
    p_shard = _shard_tree(mesh, names_tree, rules, params)
    repl = _ns(mesh, P())
    meta = {"params": sum(int(math.prod(l.shape))
                          for l in jax.tree.leaves(params)),
            "rows": c.total_rows}

    if cell.kind == "train":
        batch, b_shard = _recsys_batch(c, cell, mesh, rules)
        opt = optimizer.abstract_init(params)
        zr = _zero_rules(rules)
        opt_shard = optimizer.OptState(m=_shard_tree(mesh, names_tree, zr, params),
                                       v=_shard_tree(mesh, names_tree, zr, params),
                                       step=repl)
        ocfg = optimizer.AdamWConfig()
        loss_fns = {"deepfm": recsys.ctr_loss, "xdeepfm": recsys.ctr_loss,
                    "two_tower": recsys.two_tower_loss,
                    "bert4rec": recsys.bert4rec_loss}
        lf = loss_fns[c.kind]

        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(lf)(params, c, batch)
            new_p, new_opt, metrics = optimizer.apply(params, grads, opt, ocfg)
            return new_p, new_opt, loss, metrics

        return Cell(arch_id, cell.name, "recsys", "train", train_step,
                    (params, opt, batch), (p_shard, opt_shard, b_shard),
                    (p_shard, opt_shard, repl,
                     {"grad_norm": repl, "lr": repl}),
                    donate_argnums=(0, 1), meta=meta)

    if cell.kind == "serve":
        b = cell.global_batch
        bsh = _ns(mesh, common.resolve_pspec(("batch", None), rules, mesh))
        b1 = _ns(mesh, common.resolve_pspec(("batch",), rules, mesh))
        if c.kind in ("deepfm", "xdeepfm"):
            fn = (lambda p, ids: recsys.deepfm_logits(p, c, ids)) \
                if c.kind == "deepfm" else \
                (lambda p, ids: recsys.xdeepfm_logits(p, c, ids))
            args = (params, SDS((b, c.n_sparse), jnp.int32))
            return Cell(arch_id, cell.name, "recsys", "serve", fn, args,
                        (p_shard, bsh), b1, (), meta)
        if c.kind == "two_tower":
            cand = SDS((c.n_items, c.tower_mlp[-1]), jnp.float32)
            cand_sh = _ns(mesh, common.resolve_pspec(("candidates", None),
                                                     rules, mesh))

            def serve(params, user_ids, user_mask, cand_emb):
                u = recsys.tower_embed(params, c, "user_table", "user_mlp",
                                       user_ids, user_mask)
                v, i = recsys.sharded_streaming_topk(u, cand_emb, 100)
                return v, i

            args = (params, SDS((b, c.n_user_feats), jnp.int32),
                    SDS((b, c.n_user_feats), jnp.float32), cand)
            return Cell(arch_id, cell.name, "recsys", "serve", serve, args,
                        (p_shard, bsh, bsh, cand_sh), (bsh, bsh), (), meta)
        # bert4rec serve: next-item scores against the full item corpus
        def serve_b4r(params, items):
            h = recsys.bert4rec_hidden(params, c, items)[:, -1]   # (B, D)
            v, i = recsys.sharded_streaming_topk(h, params["item_embed"], 100)
            return v, i

        args = (params, SDS((b, c.seq_len), jnp.int32))
        return Cell(arch_id, cell.name, "recsys", "serve", serve_b4r, args,
                    (p_shard, bsh), (bsh, bsh), (), meta)

    # retrieval_cand
    n_cand = _round_up(extras_dict(cell)["n_candidates"],
                       max(int(mesh.devices.size), 512))
    if c.kind == "two_tower":
        cand_sh = _ns(mesh, common.resolve_pspec(("candidates", None), rules,
                                                 mesh))

        def retrieve(params, user_ids, user_mask, cand_emb, budget):
            u = recsys.tower_embed(params, c, "user_table", "user_mlp",
                                   user_ids, user_mask)
            v, i = recsys.anytime_retrieval(u, cand_emb, budget, 1000)
            return v, i

        args = (params, SDS((1, c.n_user_feats), jnp.int32),
                SDS((1, c.n_user_feats), jnp.float32),
                SDS((n_cand, c.tower_mlp[-1]), jnp.float32),
                SDS((), jnp.int32))
        return Cell(arch_id, cell.name, "recsys", "retrieval", retrieve, args,
                    (p_shard, _ns(mesh, P()), _ns(mesh, P()), cand_sh,
                     _ns(mesh, P())), (_ns(mesh, P()), _ns(mesh, P())), (),
                    meta)
    if c.kind in ("deepfm", "xdeepfm"):
        fn0 = recsys.deepfm_logits if c.kind == "deepfm" \
            else recsys.xdeepfm_logits
        csh = _ns(mesh, common.resolve_pspec(("candidates", None), rules,
                                             mesh))
        c1 = _ns(mesh, common.resolve_pspec(("candidates",), rules, mesh))

        def retrieve_ctr(params, ids):
            scores = fn0(params, c, ids)
            v, i = jax.lax.top_k(scores, 1000)
            return v, i

        args = (params, SDS((n_cand, c.n_sparse), jnp.int32))
        return Cell(arch_id, cell.name, "recsys", "retrieval", retrieve_ctr,
                    args, (p_shard, csh), (_ns(mesh, P()), _ns(mesh, P())),
                    (), meta)
    # bert4rec retrieval: one user history scored against all items
    def retrieve_b4r(params, items):
        h = recsys.bert4rec_hidden(params, c, items)[:, -1]
        v, i = recsys.sharded_streaming_topk(h, params["item_embed"], 1000)
        return v[0], i[0]

    args = (params, SDS((1, c.seq_len), jnp.int32))
    return Cell(arch_id, cell.name, "recsys", "retrieval", retrieve_b4r, args,
                (p_shard, _ns(mesh, P())), (_ns(mesh, P()), _ns(mesh, P())),
                (), meta)


# ---------------------------------------------------------------------------
# ISN (the paper's architecture)
# ---------------------------------------------------------------------------

def _isn_cell(arch_id, config, cell: ShapeCell, mesh, rules) -> Cell:
    from repro.isn import shard as isn_shard
    return isn_shard.build_serve_cell(arch_id, config, cell, mesh, rules,
                                      Cell)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def build_cell(arch_id: str, shape_name: str, mesh,
               rules_override: dict | None = None,
               config_override=None) -> Cell:
    config, family = registry.get_arch(arch_id)
    if config_override is not None:
        config = config_override
    cell = FAMILY_SHAPES[family][shape_name]
    rules = rules_for(family, cell)
    if rules_override:
        rules.update(rules_override)
    if family == "lm":
        return _lm_cell(arch_id, config, cell, mesh, rules)
    if family == "gnn":
        return _gnn_cell(arch_id, config, cell, mesh, rules)
    if family == "recsys":
        return _recsys_cell(arch_id, config, cell, mesh, rules)
    if family == "isn":
        return _isn_cell(arch_id, config, cell, mesh, rules)
    raise ValueError(family)
