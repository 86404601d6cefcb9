"""Decoder-only transformer family: dense / MoE, GQA, optional SWA.

Functional, layer-stacked params (leading L axis) consumed by lax.scan so a
48-layer model lowers to one HLO loop — essential for dry-run compile times
and for clean pipeline-style sharding. Covers all five assigned LM archs:
llama4-scout (MoE 16e top-1 + shared), moonshot/moonlight (MoE 64e top-6 +
shared), stablelm-3b / command-r-plus (dense GQA), h2o-danube (dense + SWA).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.common import rms_norm, cross_entropy_loss
from repro.models.attention import rope, flash_attention, decode_attention
from repro.kernels.ops import swa_attention_decode

# Optional activation-sharding hook (sequence parallelism): the launcher
# installs a with_sharding_constraint here so the layer-scan carry is
# sequence-sharded over the 'model' axis between blocks (Megatron-SP) —
# required to fit 100B-scale training activations. None = no constraint.
_ACT_SHARD = None
_ATTN_SHARD = None  # fn(tensor, role) with role in {"q", "k", "v"}
_MOE_SPMD = None    # {"mesh": Mesh, "token_axes": tuple, "expert_axis": str}


def set_activation_sharding(fn) -> None:
    global _ACT_SHARD
    _ACT_SHARD = fn


def set_attn_sharding(fn) -> None:
    """Install a per-role constraint on post-RoPE q/k/v (B, S, H, D).

    The launcher uses this to pin the baseline attention layout:
    q sequence-sharded over 'model' (sequence-parallel attention — head
    counts like llama4's 40q/8kv don't divide a 16-way TP axis), k/v
    batch-sharded only."""
    global _ATTN_SHARD
    _ATTN_SHARD = fn


def _shard_act(x):
    return _ACT_SHARD(x) if _ACT_SHARD is not None else x


def _shard_attn(x, role):
    return _ATTN_SHARD(x, role) if _ATTN_SHARD is not None else x


def set_moe_spmd(mesh=None, x_spec=None, expert_axis="model") -> None:
    """Install the expert-parallel SPMD layout for MoE layers.

    With this set, moe_ffn runs its dispatch inside shard_map: each device
    packs its local tokens into per-expert capacity buffers, a tiled
    all-to-all over `expert_axis` moves buffers to the expert owners, expert
    GEMMs run locally, and the reverse all-to-all brings outputs home. This
    is canonical DPxEP — without it GSPMD replicates the (E*cap, d) scatter
    buffer on every device (a ~16 GB/dev blow-up at moonshot train scale).

    `x_spec` is the PartitionSpec of the (B, S, d) activations entering the
    layer (e.g. P(('data',), 'model', None) under sequence parallelism).
    The body flattens tokens LOCALLY — flattening before shard_map would
    create a (B-shard x S-shard) interleaved 1-D layout GSPMD can only
    reach by full replication ("involuntary full rematerialization").
    """
    global _MOE_SPMD
    if mesh is None:
        _MOE_SPMD = None
    else:
        _MOE_SPMD = {"mesh": mesh, "x_spec": x_spec, "expert_axis": expert_axis}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str = "lm"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 1024
    # MoE (n_experts == 0 -> dense FFN)
    n_experts: int = 0
    top_k: int = 1
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    # attention
    sliding_window: int | None = None   # SWA width (None = full attention)
    rope_theta: float = 10000.0
    # numerics
    dtype: str = "bfloat16"
    q_chunk: int = 512
    kv_chunk: int = 1024
    tie_embeddings: bool = False
    # scan unroll factor for the layer loop. 1 = rolled while-loop (fast
    # compile, production path). n_layers = fully unrolled — used by the
    # dry-run analysis because XLA cost_analysis counts a while body ONCE,
    # so rolled loops under-report FLOPs/collectives by ~n_layers x.
    scan_unroll: int = 1
    # unroll the flash-attention q/kv chunk scans too (analysis mode only;
    # combine with larger q_chunk/kv_chunk to keep trip counts small)
    attn_unroll: bool = False
    # SWA decode strategy: "window_kernel" = slice the cache window + Pallas
    # kernel (O(window) compute; re-gathers across a sequence-sharded cache);
    # "masked_full" = masked full-cache attention (flash-decoding layout:
    # shard-local partials + psum — ~zero collective bytes). §Perf H2.
    decode_swa_mode: str = "window_kernel"

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab
        attn = d * self.n_heads * self.d_head + 2 * d * self.n_kv_heads * self.d_head \
            + self.n_heads * self.d_head * d
        if self.n_experts:
            ffn = self.n_experts * 3 * d * f + d * self.n_experts
            ffn += self.n_shared_experts * 3 * d * f
        else:
            ffn = 3 * d * f
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + v * d + (0 if self.tie_embeddings else v * d) + d

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if not self.n_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        full = self.param_count()
        all_experts = self.n_layers * self.n_experts * 3 * d * f
        active = self.n_layers * self.top_k * 3 * d * f
        return full - all_experts + active


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    hd, kv = cfg.d_head, cfg.n_kv_heads
    L = cfg.n_layers
    keys = iter(jax.random.split(rng, 16))
    dt = cfg.jdtype

    def w(key, *shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dt)

    p = {
        "embed": w(next(keys), v, d, fan_in=d),
        "final_norm": jnp.ones((d,), dt),
        "wq": w(next(keys), L, d, cfg.n_heads * hd, fan_in=d),
        "wk": w(next(keys), L, d, kv * hd, fan_in=d),
        "wv": w(next(keys), L, d, kv * hd, fan_in=d),
        "wo": w(next(keys), L, cfg.n_heads * hd, d, fan_in=cfg.n_heads * hd),
        "attn_norm": jnp.ones((L, d), dt),
        "ffn_norm": jnp.ones((L, d), dt),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = w(next(keys), d, v, fan_in=d)
    if cfg.n_experts:
        p["router"] = w(next(keys), L, d, cfg.n_experts, fan_in=d)
        p["moe_w1"] = w(next(keys), L, cfg.n_experts, d, f, fan_in=d)
        p["moe_w3"] = w(next(keys), L, cfg.n_experts, d, f, fan_in=d)
        p["moe_w2"] = w(next(keys), L, cfg.n_experts, f, d, fan_in=f)
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            p["shared_w1"] = w(next(keys), L, d, fs, fan_in=d)
            p["shared_w3"] = w(next(keys), L, d, fs, fan_in=d)
            p["shared_w2"] = w(next(keys), L, fs, d, fan_in=fs)
    else:
        p["ffn_w1"] = w(next(keys), L, d, f, fan_in=d)
        p["ffn_w3"] = w(next(keys), L, d, f, fan_in=d)
        p["ffn_w2"] = w(next(keys), L, f, d, fan_in=f)
    return p


# ---------------------------------------------------------------- MoE FFN

def _moe_dispatch(x, router, e: int, k: int, cap: int):
    """Top-k routing + sort-free capacity ranking for the LOCAL token shard.

    Rank of token t within expert e = number of earlier (token-order)
    assignments to e — an exclusive prefix sum over the (T, E) one-hot.
    Equivalent to the stable-sort formulation but far cheaper for XLA to
    partition than an argsort. Returns (flat_slot, flat_t, flat_w, keep).
    """
    t = x.shape[0]
    logits = (x @ router).astype(jnp.float32)                       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                          # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    assign = jax.nn.one_hot(top_i, e, dtype=jnp.int32).sum(axis=1)  # (T, E)
    before = jnp.cumsum(assign, axis=0) - assign                    # exclusive
    rank = jnp.take_along_axis(before, top_i, axis=1)               # (T, k)
    keep = rank < cap
    slot = jnp.where(keep, top_i * cap + jnp.minimum(rank, cap - 1), e * cap)
    return slot.reshape(-1), jnp.repeat(jnp.arange(t), k), (top_p * keep).reshape(-1), keep


def _moe_pack(x, flat_slot, flat_t, keep, e: int, cap: int):
    d = x.shape[1]
    return jnp.zeros((e * cap + 1, d), x.dtype).at[flat_slot].set(
        x[flat_t] * keep.reshape(-1, 1).astype(x.dtype), mode="drop"
    )[: e * cap].reshape(e, cap, d)


def _moe_expert_mlp(buf, w1, w3, w2):
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, w1)
    ) * jnp.einsum("ecd,edf->ecf", buf, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _moe_combine(out_buf_flat, flat_slot, flat_t, flat_w, t, d, e, cap, dtype):
    contrib = out_buf_flat[jnp.minimum(flat_slot, e * cap - 1)] * flat_w[:, None].astype(dtype)
    return jnp.zeros((t, d), dtype).at[flat_t].add(contrib)


def _moe_cap(t: int, k: int, e: int, cf: float) -> int:
    cap = int(cf * t * k / e) + 1
    return min(max(((cap + 3) // 4) * 4, 4), t * k)


def _moe_ffn_spmd(x3, layer, cfg: TransformerConfig):
    """Expert-parallel MoE via shard_map (see set_moe_spmd): local dispatch,
    tiled all-to-all to expert owners over the expert axis, local expert
    GEMMs, reverse all-to-all, local combine — canonical DPxEP.

    x3: the UNFLATTENED (B, S, d) activations; tokens are flattened inside
    the shard_map body so the token layout is whatever (B, S) tiling the
    surrounding program already uses."""
    from jax.sharding import PartitionSpec as P

    mesh = _MOE_SPMD["mesh"]
    x_spec = _MOE_SPMD["x_spec"]
    ea = _MOE_SPMD["expert_axis"]
    n_tok_shards = 1
    for ax in x_spec[:2]:
        for a in (ax if isinstance(ax, tuple) else ((ax,) if ax else ())):
            n_tok_shards *= mesh.shape[a]
    b, s_len, d = x3.shape
    e, k = cfg.n_experts, cfg.top_k
    t_loc = max(b * s_len // n_tok_shards, 1)
    cap_loc = _moe_cap(t_loc, k, e, cfg.capacity_factor)

    def body(x_loc3, router, w1, w3, w2):
        bl, sl, _ = x_loc3.shape
        x_loc = x_loc3.reshape(bl * sl, d)  # LOCAL flatten: no resharding
        tl = x_loc.shape[0]
        fs, ft, fw, keep = _moe_dispatch(x_loc, router, e, k, cap_loc)
        buf = _moe_pack(x_loc, fs, ft, keep, e, cap_loc)
        # ship buffers to expert owners: (E, cap, d) -> (E/tp, tp*cap, d)
        buf = jax.lax.all_to_all(buf, ea, split_axis=0, concat_axis=1, tiled=True)
        out = _moe_expert_mlp(buf, w1, w3, w2)
        # bring outputs home: (E/tp, tp*cap, d) -> (E, cap, d)
        out = jax.lax.all_to_all(out, ea, split_axis=1, concat_axis=0, tiled=True)
        y = _moe_combine(out.reshape(e * cap_loc, d), fs, ft, fw, tl, d, e,
                         cap_loc, x_loc.dtype)
        return y.reshape(bl, sl, d)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(), P(ea, None, None), P(ea, None, None),
                  P(ea, None, None)),
        out_specs=x_spec,
        check_vma=False,
    )(x3, layer["router"], layer["moe_w1"], layer["moe_w3"], layer["moe_w2"])


def moe_ffn(
    x3: jnp.ndarray, layer: dict, cfg: TransformerConfig
) -> jnp.ndarray:
    """Capacity-factor top-k MoE. x3: (B, S, d).

    Single-device path: dispatch into one (E, C, d) buffer, batched expert
    SwiGLU GEMMs, weighted combine. When set_moe_spmd() is active, the
    dispatch runs expert-parallel inside shard_map instead.
    """
    b, s_len, d = x3.shape
    e, k = cfg.n_experts, cfg.top_k
    if _MOE_SPMD is not None:
        out = _moe_ffn_spmd(x3, layer, cfg)
    else:
        x = x3.reshape(b * s_len, d)
        t = x.shape[0]
        cap = _moe_cap(t, k, e, cfg.capacity_factor)
        fs, ft, fw, keep = _moe_dispatch(x, layer["router"], e, k, cap)
        buf = _moe_pack(x, fs, ft, keep, e, cap)
        out_buf = _moe_expert_mlp(buf, layer["moe_w1"], layer["moe_w3"], layer["moe_w2"])
        out = _moe_combine(out_buf.reshape(e * cap, d), fs, ft, fw, t, d, e,
                           cap, x.dtype).reshape(b, s_len, d)

    if cfg.n_shared_experts:
        hs = jax.nn.silu(x3 @ layer["shared_w1"]) * (x3 @ layer["shared_w3"])
        out = out + hs @ layer["shared_w2"]
    return out


def dense_ffn(x: jnp.ndarray, layer: dict) -> jnp.ndarray:
    h = jax.nn.silu(x @ layer["ffn_w1"]) * (x @ layer["ffn_w3"])
    return h @ layer["ffn_w2"]


# ------------------------------------------------------------- layer step

def _split_layers(params: dict) -> tuple[dict, dict]:
    """Split params into layer-stacked (scanned) and global parts."""
    layer_keys = {
        "wq", "wk", "wv", "wo", "attn_norm", "ffn_norm",
        "router", "moe_w1", "moe_w2", "moe_w3",
        "shared_w1", "shared_w2", "shared_w3",
        "ffn_w1", "ffn_w2", "ffn_w3",
    }
    layers = {k: v for k, v in params.items() if k in layer_keys}
    glob = {k: v for k, v in params.items() if k not in layer_keys}
    return layers, glob


def _attn(x, layer, cfg: TransformerConfig, positions, k_cache=None, v_cache=None,
          cache_pos=None, mode="train"):
    b, s, d = x.shape
    hd, kv = cfg.d_head, cfg.n_kv_heads
    xq = (x @ layer["wq"]).reshape(b, s, cfg.n_heads, hd)
    xk = (x @ layer["wk"]).reshape(b, s, kv, hd)
    xv = (x @ layer["wv"]).reshape(b, s, kv, hd)
    xq = rope(xq, positions, cfg.rope_theta)
    xk = rope(xk, positions, cfg.rope_theta)
    if mode in ("train", "prefill"):
        xq = _shard_attn(xq, "q")
        xk = _shard_attn(xk, "k")
        xv = _shard_attn(xv, "v")

    if mode in ("train", "prefill"):
        out = flash_attention(
            xq, xk, xv, causal=True, window=cfg.sliding_window,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            unroll=cfg.attn_unroll,
        )
        new_k, new_v = xk, xv
    else:  # decode: s == 1, write into cache then attend
        k_cache = jax.vmap(
            lambda c, upd, p: jax.lax.dynamic_update_slice(c, upd, (p, 0, 0))
        )(k_cache, xk, cache_pos)
        v_cache = jax.vmap(
            lambda c, upd, p: jax.lax.dynamic_update_slice(c, upd, (p, 0, 0))
        )(v_cache, xv, cache_pos)
        fill = cache_pos + 1
        if cfg.sliding_window is not None and cfg.decode_swa_mode == "window_kernel":
            groups = cfg.n_heads // kv
            qg = xq[:, 0].reshape(b, kv, groups, hd)
            og = swa_attention_decode(
                qg, k_cache, v_cache, fill, window=cfg.sliding_window
            )
            out = og.reshape(b, 1, cfg.n_heads, hd)
        else:
            out = decode_attention(xq, k_cache, v_cache, fill,
                                   window=cfg.sliding_window)
        new_k, new_v = k_cache, v_cache
    out = out.reshape(b, s, cfg.n_heads * hd) @ layer["wo"]
    return out, new_k, new_v


def _layer_step(x, layer, cfg: TransformerConfig, positions, mode,
                k_cache=None, v_cache=None, cache_pos=None):
    h, new_k, new_v = _attn(
        rms_norm(x, layer["attn_norm"]), layer, cfg, positions,
        k_cache, v_cache, cache_pos, mode,
    )
    x = x + h
    y = rms_norm(x, layer["ffn_norm"])
    if cfg.n_experts:
        f = moe_ffn(y, layer, cfg)
    else:
        f = dense_ffn(y, layer)
    return x + f, new_k, new_v


# ------------------------------------------------------------ public API

def forward_train(params: dict, tokens: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """tokens (B, S) -> logits (B, S, V)."""
    layers, glob = _split_layers(params)
    b, s = tokens.shape
    x = _shard_act(glob["embed"][tokens].astype(cfg.jdtype))
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def body(x, layer):
        x, _, _ = _layer_step(x, layer, cfg, positions, "train")
        return _shard_act(x), None

    x, _ = jax.lax.scan(body, x, layers, unroll=cfg.scan_unroll)
    x = rms_norm(x, glob["final_norm"])
    unembed = glob["embed"].T if cfg.tie_embeddings else glob["unembed"]
    return (x @ unembed).astype(jnp.float32)


def loss_fn(params: dict, batch: dict, cfg: TransformerConfig) -> jnp.ndarray:
    logits = forward_train(params, batch["tokens"], cfg)
    return cross_entropy_loss(logits, batch["labels"], batch.get("mask"))


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {
        "k": jnp.zeros(shape, cfg.jdtype),
        "v": jnp.zeros(shape, cfg.jdtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def forward_prefill(params: dict, tokens: jnp.ndarray, cfg: TransformerConfig,
                    max_len: int) -> tuple[jnp.ndarray, dict]:
    """Prefill: run the full prompt, return last-token logits + KV cache."""
    layers, glob = _split_layers(params)
    b, s = tokens.shape
    x = glob["embed"][tokens].astype(cfg.jdtype)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, layer):
        h, new_k, new_v = _layer_step(x, layer, cfg, positions, "prefill")
        return h, (new_k, new_v)

    x, (ks, vs) = jax.lax.scan(body, x, layers, unroll=cfg.scan_unroll)
    x = rms_norm(x, glob["final_norm"])
    unembed = glob["embed"].T if cfg.tie_embeddings else glob["unembed"]
    logits = (x[:, -1:] @ unembed).astype(jnp.float32)
    pad = max_len - s
    cache = {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "pos": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


def forward_decode(params: dict, tokens: jnp.ndarray, cache: dict,
                   cfg: TransformerConfig) -> tuple[jnp.ndarray, dict]:
    """One decode step. tokens (B, 1); cache from init_cache/prefill."""
    layers, glob = _split_layers(params)
    x = glob["embed"][tokens].astype(cfg.jdtype)
    positions = cache["pos"][:, None]

    def body(carry, inputs):
        x = carry
        layer, k_c, v_c = inputs
        h, new_k, new_v = _layer_step(
            x, layer, cfg, positions, "decode", k_c, v_c, cache["pos"]
        )
        return h, (new_k, new_v)

    x, (new_ks, new_vs) = jax.lax.scan(
        body, x, (layers, cache["k"], cache["v"]), unroll=cfg.scan_unroll
    )
    x = rms_norm(x, glob["final_norm"])
    unembed = glob["embed"].T if cfg.tie_embeddings else glob["unembed"]
    logits = (x @ unembed).astype(jnp.float32)
    new_cache = {"k": new_ks, "v": new_vs, "pos": cache["pos"] + 1}
    return logits, new_cache
