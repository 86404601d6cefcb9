"""GNN zoo: EGNN, MeshGraphNet, SchNet, GraphSAGE.

Message passing is implemented over an explicit edge index (src, dst) via
gather -> compute -> jax.ops.segment_sum, the TPU-native formulation of
SpMM-style aggregation (JAX sparse is BCOO-only; segment ops over edge lists
ARE the message-passing substrate here, per the assignment brief). All
shapes are static: edge arrays are padded with self-loops masked to zero
weight where needed.

Each model exposes init(rng, cfg) and apply(params, batch) plus a loss; the
batch dict always carries:
  x         (N, F)   node features
  edge_src  (E,)     int32
  edge_dst  (E,)     int32
  edge_mask (E,)     float — 0 for padding edges
plus model-specific extras (coords, edge features, targets...).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.common import mlp_init, mlp_apply


def segment_mean(data, segment_ids, num_segments, mask=None):
    if mask is not None:
        data = data * mask[:, None]
        ones = mask
    else:
        ones = jnp.ones(data.shape[0], data.dtype)
    tot = jax.ops.segment_sum(data, segment_ids, num_segments)
    cnt = jax.ops.segment_sum(ones, segment_ids, num_segments)
    return tot / jnp.maximum(cnt, 1.0)[:, None]


# =====================================================================
# EGNN [Satorras et al., arXiv:2102.09844] — E(n)-equivariant
# =====================================================================

@dataclasses.dataclass(frozen=True)
class EGNNConfig:
    name: str = "egnn"
    n_layers: int = 4
    d_hidden: int = 64
    d_in: int = 16
    d_coord: int = 3
    d_out: int = 1


def egnn_init(rng, cfg: EGNNConfig) -> dict:
    keys = jax.random.split(rng, cfg.n_layers * 3 + 2)
    h = cfg.d_hidden
    params: dict = {
        "embed": mlp_init(keys[0], [cfg.d_in, h]),
        "readout": mlp_init(keys[1], [h, h, cfg.d_out]),
    }
    for i in range(cfg.n_layers):
        params[f"edge_{i}"] = mlp_init(keys[2 + 3 * i], [2 * h + 1, h, h])
        params[f"coord_{i}"] = mlp_init(keys[3 + 3 * i], [h, h, 1])
        params[f"node_{i}"] = mlp_init(keys[4 + 3 * i], [2 * h, h, h])
    return params


def egnn_apply(params: dict, batch: dict, cfg: EGNNConfig):
    x = batch["coords"]                       # (N, 3)
    src, dst = batch["edge_src"], batch["edge_dst"]
    emask = batch["edge_mask"]
    n = x.shape[0]
    h = mlp_apply(params["embed"], batch["x"])
    for i in range(cfg.n_layers):
        diff = x[src] - x[dst]                 # (E, 3)
        d2 = jnp.sum(diff * diff, axis=-1, keepdims=True)
        m = mlp_apply(
            params[f"edge_{i}"],
            jnp.concatenate([h[src], h[dst], d2], axis=-1),
            act=jax.nn.silu,
        ) * emask[:, None]
        # coordinate update (normalized difference * scalar gate)
        gate = mlp_apply(params[f"coord_{i}"], m, act=jax.nn.silu)
        # sqrt(d2 + eps): the bare sqrt has an infinite gradient at
        # coincident nodes (self-loop padding edges hit this exactly)
        upd = diff / (jnp.sqrt(d2 + 1e-8) + 1.0) * gate * emask[:, None]
        x = x + jax.ops.segment_sum(upd, dst, n) / jnp.maximum(
            jax.ops.segment_sum(emask, dst, n), 1.0
        )[:, None]
        agg = jax.ops.segment_sum(m, dst, n)
        h = h + mlp_apply(
            params[f"node_{i}"], jnp.concatenate([h, agg], axis=-1), act=jax.nn.silu
        )
    out = mlp_apply(params["readout"], h)
    return out, x


def egnn_loss(params, batch, cfg: EGNNConfig):
    pred, coords = egnn_apply(params, batch, cfg)
    nm = batch.get("node_mask")
    err = jnp.square(pred - batch["target"]).sum(-1)
    if nm is not None:
        return (err * nm).sum() / jnp.maximum(nm.sum(), 1.0)
    return err.mean()


# =====================================================================
# MeshGraphNet [Pfaff et al., arXiv:2010.03409]
# =====================================================================

@dataclasses.dataclass(frozen=True)
class MeshGraphNetConfig:
    name: str = "meshgraphnet"
    n_layers: int = 15
    d_hidden: int = 128
    mlp_layers: int = 2
    d_node_in: int = 8
    d_edge_in: int = 4
    d_out: int = 3


def _mgn_mlp_sizes(cfg: MeshGraphNetConfig, d_in: int) -> list[int]:
    return [d_in] + [cfg.d_hidden] * cfg.mlp_layers


def mgn_init(rng, cfg: MeshGraphNetConfig) -> dict:
    keys = jax.random.split(rng, 2 * cfg.n_layers + 3)
    h = cfg.d_hidden
    params: dict = {
        "node_enc": mlp_init(keys[0], _mgn_mlp_sizes(cfg, cfg.d_node_in)),
        "edge_enc": mlp_init(keys[1], _mgn_mlp_sizes(cfg, cfg.d_edge_in)),
        "decoder": mlp_init(keys[2], [h, h, cfg.d_out]),
    }
    for i in range(cfg.n_layers):
        params[f"edge_{i}"] = mlp_init(keys[3 + 2 * i], _mgn_mlp_sizes(cfg, 3 * h))
        params[f"node_{i}"] = mlp_init(keys[4 + 2 * i], _mgn_mlp_sizes(cfg, 2 * h))
    return params


def mgn_apply(params: dict, batch: dict, cfg: MeshGraphNetConfig):
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    n = batch["x"].shape[0]
    h = mlp_apply(params["node_enc"], batch["x"], act=jax.nn.relu)
    e = mlp_apply(params["edge_enc"], batch["edge_attr"], act=jax.nn.relu)
    for i in range(cfg.n_layers):
        e_new = mlp_apply(
            params[f"edge_{i}"],
            jnp.concatenate([e, h[src], h[dst]], axis=-1),
            act=jax.nn.relu,
        )
        e = e + e_new * emask[:, None]
        agg = jax.ops.segment_sum(e * emask[:, None], dst, n)  # sum aggregator
        h = h + mlp_apply(
            params[f"node_{i}"], jnp.concatenate([h, agg], axis=-1), act=jax.nn.relu
        )
    return mlp_apply(params["decoder"], h)


def mgn_loss(params, batch, cfg: MeshGraphNetConfig):
    pred = mgn_apply(params, batch, cfg)
    nm = batch.get("node_mask")
    err = jnp.square(pred - batch["target"]).sum(-1)
    if nm is not None:
        return (err * nm).sum() / jnp.maximum(nm.sum(), 1.0)
    return err.mean()


# =====================================================================
# SchNet [Schütt et al., arXiv:1706.08566]
# =====================================================================

@dataclasses.dataclass(frozen=True)
class SchNetConfig:
    name: str = "schnet"
    n_interactions: int = 3
    d_hidden: int = 64
    n_rbf: int = 300
    cutoff: float = 10.0
    n_species: int = 32
    d_out: int = 1


def schnet_init(rng, cfg: SchNetConfig) -> dict:
    keys = jax.random.split(rng, cfg.n_interactions * 3 + 2)
    h = cfg.d_hidden
    params: dict = {
        "species_embed": jax.random.normal(keys[0], (cfg.n_species, h)) * 0.1,
        "readout": mlp_init(keys[1], [h, h // 2, cfg.d_out]),
    }
    for i in range(cfg.n_interactions):
        params[f"filter_{i}"] = mlp_init(keys[2 + 3 * i], [cfg.n_rbf, h, h])
        params[f"in_{i}"] = mlp_init(keys[3 + 3 * i], [h, h])
        params[f"out_{i}"] = mlp_init(keys[4 + 3 * i], [h, h, h])
    return params


def _rbf_expand(dist: jnp.ndarray, n_rbf: int, cutoff: float) -> jnp.ndarray:
    centers = jnp.linspace(0.0, cutoff, n_rbf)
    gamma = 10.0 / cutoff
    return jnp.exp(-gamma * jnp.square(dist[:, None] - centers[None, :]))


def schnet_apply(params: dict, batch: dict, cfg: SchNetConfig):
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    coords = batch["coords"]
    n = coords.shape[0]
    h = params["species_embed"][batch["species"]]
    dist = jnp.sqrt(
        jnp.sum(jnp.square(coords[src] - coords[dst]), axis=-1) + 1e-12
    )
    rbf = _rbf_expand(dist, cfg.n_rbf, cfg.cutoff)
    # smooth cutoff envelope
    env = 0.5 * (jnp.cos(jnp.pi * jnp.clip(dist / cfg.cutoff, 0, 1)) + 1.0)
    w_cut = env * emask
    for i in range(cfg.n_interactions):
        filt = mlp_apply(params[f"filter_{i}"], rbf, act=jax.nn.softplus)  # (E, h)
        x = mlp_apply(params[f"in_{i}"], h)
        msg = x[src] * filt * w_cut[:, None]   # cfconv
        agg = jax.ops.segment_sum(msg, dst, n)
        h = h + mlp_apply(params[f"out_{i}"], agg, act=jax.nn.softplus)
    return mlp_apply(params["readout"], h)


def schnet_loss(params, batch, cfg: SchNetConfig):
    pred = schnet_apply(params, batch, cfg)
    # molecule-level energy: sum node contributions per graph then MSE
    graph_id = batch["graph_id"]
    n_graphs = batch["n_graphs"]
    energy = jax.ops.segment_sum(pred[:, 0], graph_id, n_graphs)
    return jnp.mean(jnp.square(energy - batch["target"]))


# =====================================================================
# GraphSAGE [Hamilton et al., arXiv:1706.02216] — mean aggregator
# =====================================================================

@dataclasses.dataclass(frozen=True)
class GraphSAGEConfig:
    name: str = "graphsage"
    n_layers: int = 2
    d_hidden: int = 128
    d_in: int = 602
    n_classes: int = 41
    sample_sizes: tuple[int, ...] = (25, 10)


def sage_init(rng, cfg: GraphSAGEConfig) -> dict:
    keys = jax.random.split(rng, cfg.n_layers + 1)
    params: dict = {}
    d_prev = cfg.d_in
    for i in range(cfg.n_layers):
        params[f"self_{i}"] = mlp_init(keys[i], [d_prev, cfg.d_hidden])
        params[f"nbr_{i}"] = mlp_init(keys[i], [d_prev, cfg.d_hidden])
        d_prev = cfg.d_hidden
    params["classify"] = mlp_init(keys[-1], [cfg.d_hidden, cfg.n_classes])
    return params


def sage_apply_fullgraph(params: dict, batch: dict, cfg: GraphSAGEConfig):
    """Full-graph mode: aggregate over the edge index."""
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    h = batch["x"]
    n = h.shape[0]
    for i in range(cfg.n_layers):
        agg = segment_mean(h[src], dst, n, emask)
        h = jax.nn.relu(
            mlp_apply(params[f"self_{i}"], h) + mlp_apply(params[f"nbr_{i}"], agg)
        )
        h = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
    return mlp_apply(params["classify"], h)


def sage_apply_sampled(params: dict, batch: dict, cfg: GraphSAGEConfig):
    """Sampled mode: layered feature tensors from the fanout sampler.

    batch["feats"] is a list of (B * prod(fanouts[:h]), F) feature arrays,
    deepest hop last (graphs/sampler.py layout).
    """
    feats = batch["feats"]
    fanouts = cfg.sample_sizes
    hs = list(feats)
    for i in range(cfg.n_layers):
        nxt = []
        for depth in range(len(hs) - 1):
            parent, child = hs[depth], hs[depth + 1]
            f = fanouts[depth] if depth < len(fanouts) else fanouts[-1]
            agg = child.reshape(parent.shape[0], f, -1).mean(axis=1)
            nh = jax.nn.relu(
                mlp_apply(params[f"self_{i}"], parent) + mlp_apply(params[f"nbr_{i}"], agg)
            )
            nh = nh / jnp.maximum(jnp.linalg.norm(nh, axis=-1, keepdims=True), 1e-6)
            nxt.append(nh)
        hs = nxt
    return mlp_apply(params["classify"], hs[0])


def sage_fullgraph_halo_loss(params, batch, cfg: GraphSAGEConfig, mesh, dp_axes):
    """Halo-exchange full-graph GraphSAGE (§Perf H3 — the paper's payoff).

    Nodes are row-sharded by a BuffCut placement; cross-shard (cut) edges
    read their source state from a bounded *frontier* buffer exchanged once
    per layer via all-gather of each shard's owned frontier rows. Collective
    volume per layer = Hf x d (the cut-controlled frontier) instead of the
    full N x d node-state gather GSPMD emits for the naive formulation —
    exactly the byte count the streaming partitioner minimizes.

    batch extras vs sage_loss:
      frontier_own (Hf,) int32  — LOCAL row ids each shard contributes
                                  (sharded over dp; Hf global, static cap)
      edge_src     (E,)  int32  — LOCAL index space [0, N_loc + Hf):
                                  >= N_loc means frontier slot
      edge_dst     (E,)  int32  — LOCAL dst row in [0, N_loc)
    """
    from jax.sharding import PartitionSpec as P

    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def body(params_r, x, fown, esrc, edst, emask, labels, nmask):
        n_loc = x.shape[0]
        h = x
        for i in range(cfg.n_layers):
            f_own = h[fown]                                   # (Hf_loc, d)
            frontier = jax.lax.all_gather(
                f_own, dp_axes[-1] if len(dp_axes) == 1 else dp_axes,
                tiled=True,
            )                                                 # (Hf, d)
            hx = jnp.concatenate([h, frontier], axis=0)
            agg = segment_mean(hx[esrc], edst, n_loc, emask)
            h = jax.nn.relu(
                mlp_apply(params_r[f"self_{i}"], h) + mlp_apply(params_r[f"nbr_{i}"], agg)
            )
            h = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        logits = mlp_apply(params_r["classify"], h)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        nll = (logz - gold) * nmask
        num = jax.lax.psum(nll.sum(), dp_axes)
        den = jax.lax.psum(nmask.sum(), dp_axes)
        return num / jnp.maximum(den, 1.0)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(dp, None), P(dp), P(dp), P(dp), P(dp), P(dp), P(dp)),
        out_specs=P(),
        check_vma=False,
    )(
        params, batch["x"], batch["frontier_own"], batch["edge_src"],
        batch["edge_dst"], batch["edge_mask"], batch["labels"],
        batch["node_mask"],
    )


def sage_loss(params, batch, cfg: GraphSAGEConfig):
    if "feats" in batch:
        logits = sage_apply_sampled(params, batch, cfg)
    else:
        logits = sage_apply_fullgraph(params, batch, cfg)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    nll = logz - gold
    nm = batch.get("node_mask")
    if nm is not None:
        return (nll * nm).sum() / jnp.maximum(nm.sum(), 1.0)
    return nll.mean()
