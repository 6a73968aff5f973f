"""Unified model API: init, full-sequence forward (train / prefill),
single-token decode against caches, and the LM loss.

A "batch" is a dict with keys depending on the family:
  tokens        (B,S) int32                        — always
  positions     (B,1) int32                        — decode (default cache index)
  mrope_pos     (3,B,S) int32                      — vlm (M-RoPE)
  vision_embeds (B,P,D)                            — vlm patch-embedding stub
  frames        (B,T,D)                            — audio frontend stub
For decode steps the dict carries a single token column (B,1).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, cfg.n_layers + 4)
    p: Params = {
        "embed": (jax.random.normal(ks[0], (cfg.padded_vocab(), cfg.d_model))
                  * 0.02).astype(dt),
        "final_norm": L.init_norm(ks[1], cfg, cfg.d_model),
        "blocks": [],
    }
    for i in range(cfg.n_layers):
        blk = T.init_block(ks[2 + i], cfg, i)
        if cfg.encoder is not None and cfg.block_kind(i) == "attn":
            blk = T.init_cross_attention(jax.random.fold_in(ks[2 + i], 7), cfg, blk)
        p["blocks"].append(blk)
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(ks[-2],
                                          (cfg.d_model, cfg.padded_vocab()))
                        / (cfg.d_model ** 0.5)).astype(dt)
    if cfg.encoder is not None:
        p["encoder"] = T.init_encoder(ks[-1], cfg)
    return p


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> Params:
    return {
        "layers": [T.init_block_cache(cfg, i, batch, max_len, dtype)
                   for i in range(cfg.n_layers)],
        "index": jnp.zeros((), jnp.int32),
    }


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def active_param_count(cfg: ModelConfig, params: Params) -> int:
    """MoE-aware: only top_k + shared experts count per token."""
    total = param_count(params)
    if cfg.moe is None:
        return total
    m = cfg.moe
    inactive = 0
    for i, blk in enumerate(params["blocks"]):
        if "moe" in blk:
            per_expert = sum(blk["moe"][k].size // m.n_experts
                             for k in ("w_gate", "w_up", "w_down"))
            inactive += per_expert * (m.n_experts - m.top_k)
    return total - inactive


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _embed_inputs(params: Params, batch: Dict[str, jnp.ndarray],
                  cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """Returns (x, positions, mrope_pos)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"][tokens].astype(cdt) * cfg.emb_scale
    mrope = batch.get("mrope_pos")
    if cfg.vision is not None and "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(cdt)
        x = jnp.concatenate([vis, x], axis=1)           # vision prefix
        S = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.pos_type == "mrope" and mrope is None:
        mrope = jnp.broadcast_to(pos[None], (3, B, S))  # text-only: t=h=w
    if cfg.pos_type == "learned":
        # whisper decoder learned positions approximated by sinusoidal here
        x = x + L.sinusoidal_embedding(S, cfg.d_model).astype(cdt)[None]
    x = _constrain_act(x, cfg)
    return x, pos, mrope


def _constrain_act(x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Pin the residual stream's batch dim to a mesh axis (hillclimb A3:
    GSPMD does not propagate batch sharding through the replica-vmap + layer
    scan on its own)."""
    if not cfg.act_dp_axis and not cfg.act_seq_axis:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(cfg.act_dp_axis or None, cfg.act_seq_axis or None,
             *(None,) * (x.ndim - 2))
    return jax.lax.with_sharding_constraint(x, spec)


def forward(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence forward.  Returns (logits (B,S,V), aux losses).

    When ``cfg.scan_grouping()`` applies, the repeating layer groups run
    under ``jax.lax.scan`` (compile time ~O(1) in depth — essential for the
    56-72 layer configs); otherwise a python loop."""
    x, pos, mrope = _embed_inputs(params, batch, cfg)
    cross_kv_cache = _encode_cross(params, batch, cfg)
    aux_total: Dict[str, jnp.ndarray] = {}

    def run_block(blk, x, i):
        ckv = _layer_cross_kv(blk, cross_kv_cache, cfg)
        return T.block_forward(blk, x, cfg, i, positions=pos,
                               cross_kv=ckv, mrope_pos=mrope)

    def acc_aux(aux):
        for k, v in aux.items():
            aux_total[k] = aux_total.get(k, 0.0) + v

    policy = (jax.checkpoint_policies.dots_saveable
              if cfg.remat_policy == "dots"
              else jax.checkpoint_policies.nothing_saveable)

    grouping = cfg.scan_grouping()
    prefix = cfg.n_layers if grouping is None else grouping[0]
    for i in range(prefix):
        if cfg.remat:
            y, aux, _ = jax.checkpoint(
                lambda x_, i_=i: run_block(params["blocks"][i_], x_, i_),
                policy=policy)(x)
        else:
            y, aux, _ = run_block(params["blocks"][i], x, i)
        x = y
        acc_aux(aux)

    if grouping is not None:
        _, P, G = grouping
        body = params["blocks"][prefix:]
        # stack the g-th repetition of slot j: (G, ...) leading dim per leaf
        stacked = tuple(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *[body[g * P + j] for g in range(G)])
            for j in range(P))

        def group_fn(x, grp):
            aux_g: Dict[str, jnp.ndarray] = {}
            for j in range(P):
                x, aux, _ = run_block(grp[j], x, prefix + j)
                x = _constrain_act(x, cfg)
                for k, v in aux.items():
                    aux_g[k] = aux_g.get(k, 0.0) + v
            return x, aux_g

        if cfg.remat:
            group_fn = jax.checkpoint(group_fn, policy=policy)
        x, aux_stk = jax.lax.scan(group_fn, x, stacked)
        acc_aux({k: jnp.sum(v) for k, v in aux_stk.items()})

    x = L.norm_forward(params["final_norm"], x, cfg)
    logits = _lm_head(params, x, cfg)
    return logits, aux_total


def _lm_head(params, x, cfg):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(x.dtype)) * cfg.logit_scale
    Vp = cfg.padded_vocab()
    if Vp != cfg.vocab_size:
        # mask padded columns (elementwise on the sharded vocab dim — no
        # re-gather); loss/argmax then never select them
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(col < cfg.vocab_size, logits, -1e30)
    return logits


def _encode_cross(params, batch, cfg) -> Optional[jnp.ndarray]:
    if cfg.encoder is None:
        return None
    frames = batch["frames"]
    return T.encoder_forward(params["encoder"], frames.astype(
        jnp.dtype(cfg.compute_dtype)), cfg)


def _layer_cross_kv(blk, enc_out, cfg):
    if enc_out is None or "cross" not in blk:
        return None
    B, Te, D = enc_out.shape
    k = L.dense(blk["cross"]["wk"], enc_out).reshape(
        B, Te, cfg.n_kv_heads, cfg.head_dim())
    v = L.dense(blk["cross"]["wv"], enc_out).reshape(
        B, Te, cfg.n_kv_heads, cfg.head_dim())
    return (k, v)


def decode_step(params: Params, batch: Dict[str, jnp.ndarray], caches: Params,
                cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    """One-token decode.  batch["tokens"]: (B,1).  Returns (logits (B,1,V),
    updated caches)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    assert S == 1
    idx = caches["index"]
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"][tokens].astype(cdt) * cfg.emb_scale
    pos = batch.get("positions",
                    jnp.broadcast_to(idx[None, None], (B, 1)).astype(jnp.int32))
    mrope = batch.get("mrope_pos")
    if cfg.pos_type == "mrope" and mrope is None:
        mrope = jnp.broadcast_to(pos[None], (3, B, 1))
    if cfg.pos_type == "learned":
        D = cfg.d_model
        dim = jnp.arange(D // 2, dtype=jnp.float32)
        ang = idx.astype(jnp.float32) / jnp.power(10000.0, 2 * dim / D)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])
        x = x + pe.astype(cdt)[None, None, :]
    enc_out = batch.get("encoder_out")
    new_layers = []
    for i in range(cfg.n_layers):
        blk = params["blocks"][i]
        ckv = _layer_cross_kv(blk, enc_out, cfg)
        x, _, nc = T.block_forward(blk, x, cfg, i, positions=pos,
                                   cache=caches["layers"][i], cache_index=idx,
                                   cross_kv=ckv, mrope_pos=mrope)
        new_layers.append(nc)
    x = L.norm_forward(params["final_norm"], x, cfg)
    logits = _lm_head(params, x, cfg)
    return logits, {"layers": new_layers, "index": idx + 1}


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy (+ MoE aux)."""
    logits, aux = forward(params, batch, cfg)
    tokens = batch["tokens"]
    # vision prefix (if any) is not scored
    S = tokens.shape[1]
    logits = logits[:, -S:, :]
    tgt = tokens[:, 1:]
    lg = logits[:, :-1, :].astype(jnp.float32)
    # vocab-parallel-friendly cross entropy: logsumexp + one-hot contraction
    # reduce over the (possibly 'model'-sharded) vocab dim with scalar-sized
    # collectives instead of gathering full logits (take_along_axis would).
    lse = jax.nn.logsumexp(lg, axis=-1)
    onehot = jax.nn.one_hot(tgt, lg.shape[-1], dtype=lg.dtype)
    picked = jnp.einsum("bsv,bsv->bs", lg, onehot)
    nll = lse - picked
    mask = batch.get("loss_mask", jnp.ones_like(tgt, jnp.float32))
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + sum(aux.values()) if aux else loss
    aux = dict(aux, ce_loss=loss)
    return total, aux
