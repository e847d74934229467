"""RecSys architectures: DeepFM, xDeepFM (CIN), two-tower retrieval, BERT4Rec.

Huge row-sharded embedding tables + a small interaction network — the
lookup is the hot path (see `repro.models.embedding`).  The two-tower
retrieval arch is where the paper's technique applies *directly*: its
``retrieval_cand`` shape is first-stage candidate generation, and
``anytime_retrieval`` scores popularity-ordered candidate tiles under a
ρ-style budget with a per-query predicted k — the JASS mechanism
transplanted to dense retrieval (see DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models import common, embedding
from repro.models.attention import chunked_attention


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str                      # deepfm | xdeepfm | two_tower | bert4rec
    n_sparse: int = 39
    embed_dim: int = 10
    rows_per_field: int = 1_000_000
    mlp: tuple = (400, 400, 400)
    cin_layers: tuple = ()
    # two-tower
    tower_mlp: tuple = (1024, 512, 256)
    n_users: int = 8_000_000
    n_items: int = 2_000_000
    n_user_feats: int = 16
    n_item_feats: int = 8
    # bert4rec
    seq_len: int = 200
    n_blocks: int = 2
    n_heads: int = 2
    dtype: str = "float32"
    cost_exact: bool = False

    @property
    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def total_rows(self) -> int:
        if self.kind == "two_tower":
            return self.n_users + self.n_items
        if self.kind == "bert4rec":
            return self.n_items
        return self.n_sparse * self.rows_per_field

    def param_count(self) -> int:
        p, _ = init(self, abstract=True)
        return sum(int(jnp.prod(jnp.asarray(l.shape)))
                   for l in jax.tree.leaves(p))


def _mlp_params(pf, prefix, dims):
    # interaction nets are tiny (≤ a few 100k params) — replicate; the model
    # axis is reserved for the embedding-table rows
    ps = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        ps[f"w{i}"] = pf.dense(f"{prefix}/w{i}", (a, b), (None, None))
        ps[f"b{i}"] = pf.zeros(f"{prefix}/b{i}", (b,), (None,))
    return ps


def _mlp(ps, x, act=jax.nn.relu, last_act=False):
    n = len([k for k in ps if k.startswith("w")])
    for i in range(n):
        x = x @ ps[f"w{i}"] + ps[f"b{i}"]
        if i < n - 1 or last_act:
            x = act(x)
    return x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(c: RecsysConfig, rng=None, abstract: bool = False):
    pf = common.ParamFactory(rng if rng is not None else jax.random.PRNGKey(0),
                             abstract=abstract, dtype=c.jdtype)
    d = c.embed_dim
    if c.kind in ("deepfm", "xdeepfm"):
        rows = c.n_sparse * c.rows_per_field
        params = {
            "table": pf.dense("table", (rows, d), ("rows", None), scale=0.01),
            "linear": pf.dense("linear", (rows, 1), ("rows", None), scale=0.01),
            "mlp": _mlp_params(pf, "mlp",
                               (c.n_sparse * d,) + c.mlp + (1,)),
        }
        if c.kind == "xdeepfm":
            cin = {}
            hk = c.n_sparse
            for i, h_next in enumerate(c.cin_layers):
                cin[f"w{i}"] = pf.dense(f"cin/w{i}", (hk * c.n_sparse, h_next),
                                        (None, None), scale=0.05)
                hk = h_next
            params["cin"] = cin
            params["cin_out"] = pf.dense(
                "cin_out", (sum(c.cin_layers), 1), (None, None))
        return params, pf.names

    if c.kind == "two_tower":
        d = c.tower_mlp[-1]
        de = 256
        params = {
            "user_table": pf.dense("user_table", (c.n_users, de),
                                   ("rows", None), scale=0.01),
            "item_table": pf.dense("item_table", (c.n_items, de),
                                   ("rows", None), scale=0.01),
            "user_mlp": _mlp_params(pf, "user_mlp", (de,) + c.tower_mlp),
            "item_mlp": _mlp_params(pf, "item_mlp", (de,) + c.tower_mlp),
        }
        return params, pf.names

    if c.kind == "bert4rec":
        d = c.embed_dim
        padded_items = ((c.n_items + 2 + 255) // 256) * 256
        params = {
            "item_embed": pf.dense("item_embed", (padded_items, d),
                                   ("rows", None), scale=0.02),
            "pos_embed": pf.dense("pos_embed", (c.seq_len, d), (None, None),
                                  scale=0.02),
            "blocks": common.stack_layer_params(
                lambda f, pre: {
                    "wq": f.dense(f"{pre}/wq", (d, d), (None, "heads")),
                    "wk": f.dense(f"{pre}/wk", (d, d), (None, "heads")),
                    "wv": f.dense(f"{pre}/wv", (d, d), (None, "heads")),
                    "wo": f.dense(f"{pre}/wo", (d, d), ("heads", None)),
                    "w1": f.dense(f"{pre}/w1", (d, 4 * d), (None, "ffn")),
                    "b1": f.zeros(f"{pre}/b1", (4 * d,), ("ffn",)),
                    "w2": f.dense(f"{pre}/w2", (4 * d, d), ("ffn", None)),
                    "b2": f.zeros(f"{pre}/b2", (d,), (None,)),
                    "ln1": f.ones(f"{pre}/ln1", (d,), (None,)),
                    "ln2": f.ones(f"{pre}/ln2", (d,), (None,)),
                }, pf, c.n_blocks, "blocks"),
            "final_ln": pf.ones("final_ln", (d,), (None,)),
        }
        return params, pf.names
    raise ValueError(c.kind)


# ---------------------------------------------------------------------------
# forwards
# ---------------------------------------------------------------------------

def _field_embed(params, c, ids):
    """ids (B, n_sparse) with per-field offsets already applied -> (B, F, D)."""
    return embedding.lookup(params["table"], ids)


def deepfm_logits(params, c: RecsysConfig, ids):
    e = _field_embed(params, c, ids)                        # (B, F, D)
    lin = jnp.sum(embedding.lookup(params["linear"], ids)[..., 0], axis=1)
    s = jnp.sum(e, axis=1)
    fm = 0.5 * jnp.sum(s * s - jnp.sum(e * e, axis=1), axis=-1)
    deep = _mlp(params["mlp"], e.reshape(e.shape[0], -1))[:, 0]
    return lin + fm + deep


def xdeepfm_logits(params, c: RecsysConfig, ids):
    e = _field_embed(params, c, ids)                        # (B, m, D)
    x0, xk = e, e
    pools = []
    for i in range(len(c.cin_layers)):
        z = jnp.einsum("bhd,bmd->bhmd", xk, x0)
        b, hk, m, d = z.shape
        xk = jnp.einsum("bnd,nh->bhd", z.reshape(b, hk * m, d),
                        params["cin"][f"w{i}"])
        pools.append(jnp.sum(xk, axis=-1))                  # (B, h)
    cin_term = (jnp.concatenate(pools, axis=-1) @ params["cin_out"])[:, 0]
    lin = jnp.sum(embedding.lookup(params["linear"], ids)[..., 0], axis=1)
    deep = _mlp(params["mlp"], e.reshape(e.shape[0], -1))[:, 0]
    return lin + cin_term + deep


def ctr_loss(params, c: RecsysConfig, batch):
    logit_fn = deepfm_logits if c.kind == "deepfm" else xdeepfm_logits
    logits = logit_fn(params, c, batch["ids"])
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def tower_embed(params, c: RecsysConfig, table_key, mlp_key, ids, mask):
    e = embedding.embedding_bag(params[table_key], ids, mask, mode="mean")
    z = _mlp(params[mlp_key], e, last_act=False)
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-6)


def two_tower_loss(params, c: RecsysConfig, batch, temp: float = 20.0):
    """In-batch sampled softmax with logQ correction (Yi et al. RecSys'19)."""
    u = tower_embed(params, c, "user_table", "user_mlp",
                    batch["user_ids"], batch["user_mask"])
    i = tower_embed(params, c, "item_table", "item_mlp",
                    batch["item_ids"], batch["item_mask"])
    logits = (u @ i.T) * temp - batch["log_q"][None, :]
    labels = jnp.arange(u.shape[0])
    return common.cross_entropy(logits[:, None, :], labels[:, None],
                                u.shape[0])


def retrieval_scores(params, c: RecsysConfig, query_emb, cand_emb):
    """Score one query against the candidate corpus. cand_emb is the
    precomputed item-tower output (n_cand, d), sharded over "candidates"."""
    return cand_emb @ query_emb[0]


def streaming_topk(q_emb, cand_emb, k: int, tile: int = 16384):
    """Top-k of ``q_emb @ cand_embᵀ`` without materializing the full score
    matrix: lax.scan over candidate tiles with a running (B, k) top-k merge.

    Peak transient is (B, tile) instead of (B, n_cand) — the difference
    between 2 TB and 1 GB at serve_bulk scale (EXPERIMENTS.md §Perf).
    q_emb: (B, D); cand_emb: (N, D), N % tile == 0.  Returns (vals, idx).
    """
    b, d = q_emb.shape
    n = cand_emb.shape[0]
    tile = min(tile, n)
    n_pad = (-n) % tile
    if n_pad:
        cand_emb = jnp.concatenate(
            [cand_emb, jnp.zeros((n_pad, d), cand_emb.dtype)], axis=0)
    n_tiles = (n + n_pad) // tile
    tiles = cand_emb.reshape(n_tiles, tile, d)
    bases = jnp.arange(n_tiles, dtype=jnp.int32) * tile

    def step(carry, inp):
        best_v, best_i = carry
        emb, base = inp
        s = q_emb @ emb.T                                   # (B, tile)
        idx = base + jnp.arange(tile, dtype=jnp.int32)
        s = jnp.where(idx[None, :] < n, s, -jnp.inf)        # mask padding
        v, i = jax.lax.top_k(s, min(k, tile))
        i = jnp.take(idx, i)
        v2 = jnp.concatenate([best_v, v], axis=1)
        i2 = jnp.concatenate([best_i, i], axis=1)
        v3, p = jax.lax.top_k(v2, k)
        return (v3, jnp.take_along_axis(i2, p, axis=1)), None

    init = (jnp.full((b, k), -jnp.inf, q_emb.dtype),
            jnp.zeros((b, k), jnp.int32))
    (vals, idx), _ = jax.lax.scan(step, init, (tiles, bases))
    return vals, idx


def sharded_streaming_topk(q_emb, cand_emb, k: int, tile: int = 8192):
    """Distributed retrieval top-k: each "model" shard streams its local
    candidate rows (streaming_topk), then one k-sized all-gather + merge —
    the same local-topk/merge pattern as the paper's ISN aggregation.

    Collective payload: B·k·(score,id) per shard instead of per-tile score
    gathers (ms vs hundreds of ms at serve_bulk scale, §Perf)."""
    from repro.models import common as _c
    mesh = _c.get_abstract_mesh_or_none()
    sizes = dict(mesh.shape) if mesh is not None else {}
    mw = sizes.get("model", 1)
    b, n = q_emb.shape[0], cand_emb.shape[0]
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    bw = 1
    for a in batch_axes:
        bw *= sizes[a]
    if mesh is None or mw <= 1 or n % mw or (b % bw if bw else 0):
        return streaming_topk(q_emb, cand_emb, k, tile)
    n_local = n // mw

    def local_fn(q, cand_local):
        v, i = streaming_topk(q, cand_local, k, tile)
        i = i + jax.lax.axis_index("model") * n_local
        av = jax.lax.all_gather(v, "model", axis=1, tiled=True)
        ai = jax.lax.all_gather(i, "model", axis=1, tiled=True)
        v2, p = jax.lax.top_k(av, k)
        return v2, jnp.take_along_axis(ai, p, axis=1)

    from jax.sharding import PartitionSpec as P
    qspec = P(batch_axes if batch_axes else None, None)
    return shard_map(local_fn, mesh=mesh,
                         in_specs=(qspec, P("model", None)),
                         out_specs=(qspec, qspec),
                         check_vma=False)(q_emb, cand_emb)


def anytime_retrieval(query_emb, cand_emb, prior_order_len: jnp.ndarray,
                      k: int):
    """The paper's anytime budget transplanted to dense retrieval.

    cand_emb must be stored in *popularity (impact) order*; the Stage-0
    predictor supplies a per-query budget ``prior_order_len`` (#candidates
    to score).  Scoring beyond the budget is masked, so worst-case latency
    is bounded exactly like JASS's ρ cap.
    """
    n = cand_emb.shape[0]
    scores = cand_emb @ query_emb[0]
    live = jnp.arange(n) < prior_order_len
    scores = jnp.where(live, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def bert4rec_logits(params, c: RecsysConfig, items):
    """items: (B, S) -> (B, S, n_items+2) full-vocab logits (small scales /
    serving; training uses the sampled-softmax loss below)."""
    x = bert4rec_hidden(params, c, items)
    return x @ params["item_embed"].T


def bert4rec_hidden(params, c: RecsysConfig, items):
    """items: (B, S) -> final hidden states (B, S, D)."""
    b, s = items.shape
    d = c.embed_dim
    x = embedding.lookup(params["item_embed"], items) + params["pos_embed"][None]

    def block(x, bp):
        h = common.rms_norm(x, bp["ln1"])
        q = (h @ bp["wq"]).reshape(b, s, c.n_heads, -1).transpose(0, 2, 1, 3)
        kk = (h @ bp["wk"]).reshape(b, s, c.n_heads, -1).transpose(0, 2, 1, 3)
        v = (h @ bp["wv"]).reshape(b, s, c.n_heads, -1).transpose(0, 2, 1, 3)
        o = chunked_attention(q, kk, v, causal=False)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + o @ bp["wo"]
        h = common.rms_norm(x, bp["ln2"])
        x = x + common.gelu_mlp(h, bp["w1"], bp["b1"], bp["w2"], bp["b2"])
        return x, None

    x, _ = jax.lax.scan(block, x, params["blocks"],
                        unroll=c.n_blocks if c.cost_exact else 1)
    return common.rms_norm(x, params["final_ln"])


def bert4rec_loss(params, c: RecsysConfig, batch):
    """Masked-item training with sampled softmax (full 1M-item softmax per
    masked position is infeasible; BERT4Rec evaluates with sampled negatives
    as well).  batch: items (B, S); positions (B, M) masked slots;
    candidates (C,) shared negative pool (includes the true items);
    label_idx (B, M) index of the true item within candidates."""
    h = bert4rec_hidden(params, c, batch["items"])           # (B, S, D)
    hm = jnp.take_along_axis(
        h, batch["positions"][..., None], axis=1)            # (B, M, D)
    cand = embedding.lookup(params["item_embed"], batch["candidates"])
    logits = jnp.einsum("bmd,cd->bmc", hm, cand)
    return common.cross_entropy(logits, batch["label_idx"],
                                batch["candidates"].shape[0])
