"""Core neural-net layers in pure JAX.

Everything is functional: ``init_*`` builds a param pytree (nested dicts of
jnp arrays), ``*_forward`` consumes it.  All layers support both full-sequence
(train / prefill) and single-token cached decode.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.kernels import ops as kops

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, d_in: int, d_out: int, *, dtype, bias: bool = False,
               scale: float | None = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(key, cfg: ModelConfig, d: int) -> Params:
    dt = _dtype(cfg)
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), dt)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}
    if cfg.norm_type == "nonparametric_ln":   # OLMo
        return {}
    raise ValueError(cfg.norm_type)


def norm_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if cfg.norm_type == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard, partial, and M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(rotary_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float,
               rotary_frac: float = 1.0) -> jnp.ndarray:
    """x: (B,S,H,dh); pos: (B,S) int32.  Rotates the first
    ``rotary_frac * dh`` dims (half-split convention)."""
    dh = x.shape[-1]
    rd = int(dh * rotary_frac)
    rd -= rd % 2
    inv = rope_freqs(rd, theta)                           # (rd/2,)
    ang = pos[..., None].astype(jnp.float32) * inv        # (B,S,rd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype), xp], axis=-1)


def apply_mrope(x: jnp.ndarray, pos3: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.  pos3: (3,B,S) — temporal/height/width
    position ids.  ``sections`` partitions the dh/2 frequency slots."""
    dh = x.shape[-1]
    assert sum(sections) == dh // 2, (sections, dh)
    inv = rope_freqs(dh, theta)                           # (dh/2,)
    ang = pos3[..., None].astype(jnp.float32) * inv       # (3,B,S,dh/2)
    # pick which of t/h/w drives each frequency slot
    sel = jnp.repeat(jnp.arange(3), jnp.array(sections),
                     total_repeat_length=dh // 2)         # (dh/2,)
    ang = jnp.einsum("tbsf,tf->bsf", ang, jax.nn.one_hot(sel, 3).T)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype)], axis=-1)


def sinusoidal_embedding(n_pos: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA / SWA / cross / MLA) with optional KV cache
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    D, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    ks = jax.random.split(key, 6)
    if cfg.attention_type == "mla":
        m: MLAConfig = cfg.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        p = {
            "wq": dense_init(ks[0], D, H * qk_dim, dtype=dt),
            "wkv_a": dense_init(ks[1], D, m.kv_lora_rank + m.qk_rope_head_dim, dtype=dt),
            "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), dt)},
            "wkv_b": dense_init(ks[2], m.kv_lora_rank,
                                H * (m.qk_nope_head_dim + m.v_head_dim), dtype=dt),
            "wo": dense_init(ks[3], H * m.v_head_dim, D, dtype=dt),
        }
        if m.q_lora_rank:
            p["wq_a"] = dense_init(ks[4], D, m.q_lora_rank, dtype=dt)
            p["q_norm"] = {"scale": jnp.ones((m.q_lora_rank,), dt)}
            p["wq"] = dense_init(ks[0], m.q_lora_rank, H * qk_dim, dtype=dt)
        return p
    b = cfg.attn_qkv_bias
    return {
        "wq": dense_init(ks[0], D, H * dh, dtype=dt, bias=b),
        "wk": dense_init(ks[1], D, K * dh, dtype=dt, bias=b),
        "wv": dense_init(ks[2], D, K * dh, dtype=dt, bias=b),
        "wo": dense_init(ks[3], H * dh, D, dtype=dt),
    }


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Params:
    """Fixed-size ring buffer.  For SWA the buffer is only ``window`` long."""
    if cfg.attention_type == "mla":
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
            "pos": jnp.full((batch, max_len), -1, jnp.int32),
        }
    buf = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    K, dh = cfg.n_kv_heads, cfg.head_dim()
    return {
        "k": jnp.zeros((batch, buf, K, dh), dtype),
        "v": jnp.zeros((batch, buf, K, dh), dtype),
        "pos": jnp.full((batch, buf), -1, jnp.int32),
    }


def _sdpa(q, k, v, mask, softcap: float = 0.0):
    """q:(B,Sq,H,dh) k,v:(B,Sk,K,dv) grouped-query attention core."""
    B, Sq, H, dh = q.shape
    Kh = k.shape[2]
    G = H // Kh
    q = q.reshape(B, Sq, Kh, G, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(v.dtype)


def _causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, window: int) -> jnp.ndarray:
    """q_pos (B,Sq), k_pos (B,Sk) -> (B,Sq,Sk) bool."""
    m = k_pos[:, None, :] <= q_pos[:, :, None]
    m &= k_pos[:, None, :] >= 0
    if window:
        m &= k_pos[:, None, :] > q_pos[:, :, None] - window
    return m


def _flash_path(cfg: ModelConfig, seq_len: int) -> bool:
    """Whether a full-sequence self-attention over ``seq_len`` tokens runs
    as the flash kernels: on a TPU, for the plain causal mask (no sliding
    window, no logit softcap), a shape the kernels' tiles divide, and a
    program the kernels can lower in.  The kernels mask by token index,
    which is the mask of the positions ``model.forward`` gives: 0 to S - 1.

    GSPMD cannot partition a Mosaic kernel, so it lowers only in a program
    on one device or inside a ``shard_map`` manual over every mesh axis.
    A plain jit may span every device it sees, and a ``replica_tp`` program
    leaves the ``model`` axis to GSPMD: both keep the plain core."""
    mesh = jax.sharding.get_abstract_mesh()
    lowers = (jax.device_count() == 1 if mesh.empty
              else set(mesh.manual_axes) == set(mesh.axis_names))
    return (jax.default_backend() == "tpu" and lowers
            and cfg.sliding_window == 0 and not cfg.attn_logit_softcap
            and kops.flash_block(seq_len, cfg.head_dim()) > 0)


def attention_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                      positions: jnp.ndarray,
                      cache: Optional[Params] = None,
                      cache_index: Optional[jnp.ndarray] = None,
                      cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                      mrope_pos: Optional[jnp.ndarray] = None,
                      ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Returns (output, updated_cache).

    * full-sequence: cache=None — causal (or cross) attention over x.
    * decode: cache given, x is (B,1,D), cache_index is the write slot.
    """
    if cfg.attention_type == "mla":
        return _mla_forward(p, x, cfg, positions=positions, cache=cache,
                            cache_index=cache_index)
    B, S, D = x.shape
    H, Kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    q = dense(p["wq"], x).reshape(B, S, H, dh)
    if cross_kv is not None:
        k, v = cross_kv
        mask = jnp.ones((B, S, k.shape[1]), bool)
        out = _sdpa(q, k, v, mask, cfg.attn_logit_softcap)
        return dense(p["wo"], out.reshape(B, S, H * dh)), cache
    k = dense(p["wk"], x).reshape(B, S, Kh, dh)
    v = dense(p["wv"], x).reshape(B, S, Kh, dh)
    if cfg.pos_type == "mrope":
        q = apply_mrope(q, mrope_pos, cfg.rope_theta, cfg.vision.mrope_sections)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta, cfg.vision.mrope_sections)
    elif cfg.pos_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor)

    if cache is None:
        if _flash_path(cfg, S):
            out = kops.flash_attention(q, k, v)
        else:
            mask = _causal_mask(positions, positions, cfg.sliding_window)
            out = _sdpa(q, k, v, mask, cfg.attn_logit_softcap)
        return dense(p["wo"], out.reshape(B, S, H * dh)), None

    # --- cached decode (S == 1) ---
    buf = cache["k"].shape[1]
    slot = (cache_index % buf).astype(jnp.int32)
    k_cache = _scatter_rows(cache["k"], k, slot)
    v_cache = _scatter_rows(cache["v"], v, slot)
    pos_cache = _scatter_pos(cache["pos"], positions, slot)
    mask = _causal_mask(positions, pos_cache, cfg.sliding_window)
    out = _sdpa(q, k_cache, v_cache, mask, cfg.attn_logit_softcap)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache}
    return dense(p["wo"], out.reshape(B, S, H * dh)), new_cache


def _scatter_rows(buf: jnp.ndarray, x: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """Write x (B,1,...) into buf (B,S,...) at per-batch-uniform slot."""
    return jax.lax.dynamic_update_slice(
        buf, x.astype(buf.dtype), (0, slot) + (0,) * (buf.ndim - 2))


def _scatter_pos(buf: jnp.ndarray, pos: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(buf, pos.astype(buf.dtype), (0, slot))


def _mla_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                 positions, cache=None, cache_index=None):
    """DeepSeek-V2 multi-head latent attention.  The KV cache stores only
    the compressed latent (kv_lora_rank) + shared rope key — the paper's
    beyond-baseline memory win for decode."""
    m: MLAConfig = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        cq = dense(p["wq_a"], x)
        cq = _rms(cq, p["q_norm"]["scale"], cfg.norm_eps)
        q = dense(p["wq"], cq).reshape(B, S, H, qk_dim)
    else:
        q = dense(p["wq"], x).reshape(B, S, H, qk_dim)
    qn, qr = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    qr = apply_rope(qr, positions, cfg.rope_theta)

    kv_a = dense(p["wkv_a"], x)
    ckv, kpe = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    ckv = _rms(ckv, p["kv_norm"]["scale"], cfg.norm_eps)
    kpe = apply_rope(kpe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    if cache is not None:
        slot = cache_index.astype(jnp.int32)
        ckv = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, slot, 0))
        kpe = jax.lax.dynamic_update_slice(
            cache["kpe"], kpe.astype(cache["kpe"].dtype), (0, slot, 0))
        pos_cache = _scatter_pos(cache["pos"], positions, slot)
        new_cache = {"ckv": ckv, "kpe": kpe, "pos": pos_cache}
        k_pos = pos_cache
    else:
        new_cache = None
        k_pos = positions

    kv = dense(p["wkv_b"], ckv.astype(x.dtype))
    Sk = kv.shape[1]
    kv = kv.reshape(B, Sk, H, m.qk_nope_head_dim + m.v_head_dim)
    kn, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    k = jnp.concatenate([kn, jnp.broadcast_to(kpe[:, :, None, :].astype(x.dtype),
                                              (B, Sk, H, m.qk_rope_head_dim))], axis=-1)
    q_full = jnp.concatenate([qn, qr], axis=-1)
    mask = _causal_mask(positions, k_pos, 0)
    out = _sdpa(q_full, k, v, mask, cfg.attn_logit_softcap)
    return dense(p["wo"], out.reshape(B, S, H * m.v_head_dim)), new_cache


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    dt = _dtype(cfg)
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "w_gate": dense_init(ks[0], D, F, dtype=dt),
            "w_up": dense_init(ks[1], D, F, dtype=dt),
            "w_down": dense_init(ks[2], F, D, dtype=dt),
        }
    return {  # gelu (whisper)
        "w_up": dense_init(ks[0], D, F, dtype=dt, bias=True),
        "w_down": dense_init(ks[1], F, D, dtype=dt, bias=True),
    }


def mlp_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if "w_gate" in p:
        return dense(p["w_down"], jax.nn.silu(dense(p["w_gate"], x)) * dense(p["w_up"], x))
    return dense(p["w_down"], jax.nn.gelu(dense(p["w_up"], x)))


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style grouped einsum dispatch)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig) -> Params:
    m: MoEConfig = cfg.moe
    dt = _dtype(cfg)
    D, F, E = cfg.d_model, m.d_ff_expert, m.n_experts
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(D)
    p = {
        "router": (jax.random.normal(ks[0], (D, E)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, D, F)) * s).astype(dt),
        "w_up": (jax.random.normal(ks[2], (E, D, F)) * s).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, F, D)) / math.sqrt(F)).astype(dt),
    }
    if m.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=F * m.n_shared_experts)
    return p


def moe_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                group_size: int = 256) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B,S,D) -> (out, aux_losses).  Grouped capacity-based dispatch:
    tokens are viewed as (G, Sg); each group independently routes to E
    experts with capacity C = Sg*k/E*cf.  Lowers to all-to-all when the
    expert dim is sharded over the 'model' mesh axis."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    T = B * S
    Sg = min(group_size, T)
    while T % Sg:
        Sg //= 2
    G = T // Sg
    xg = x.reshape(G, Sg, D)
    logits = (xg.astype(jnp.float32) @ p["router"])            # (G,Sg,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)        # (G,Sg,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                # renormalize

    C = max(4, int(Sg * m.top_k / m.n_experts * m.capacity_factor))
    C = min(C, Sg)
    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(gate_idx, m.n_experts, dtype=jnp.float32)  # (G,Sg,k,E)
    flat = onehot.reshape(G, Sg * m.top_k, m.n_experts)
    pos = jnp.cumsum(flat, axis=1) * flat - 1.0                # (G,Sg*k,E)
    pos = pos.reshape(G, Sg, m.top_k, m.n_experts)
    keep = (pos >= 0) & (pos < C)
    pos_e = jnp.where(keep, pos, 0).astype(jnp.int32).max(-1)  # (G,Sg,k)
    keep_k = keep.any(-1)                                      # (G,Sg,k)
    # build (G,Sg,E,C) per top-k slot to avoid a 5-D (G,Sg,k,E,C) buffer
    dispatch = jnp.zeros((G, Sg, m.n_experts, C), x.dtype)
    combine = jnp.zeros((G, Sg, m.n_experts, C), x.dtype)
    for j in range(m.top_k):
        oh_c = jax.nn.one_hot(pos_e[:, :, j], C, dtype=x.dtype) \
            * keep_k[:, :, j, None].astype(x.dtype)            # (G,Sg,C)
        oh_e = onehot[:, :, j].astype(x.dtype)                 # (G,Sg,E)
        d_j = oh_e[..., None] * oh_c[:, :, None, :]
        dispatch = dispatch + d_j
        combine = combine + gate_vals[:, :, j, None, None].astype(x.dtype) * d_j

    ex_in = jnp.einsum("gsec,gsd->egcd", dispatch, xg)         # (E,G,C,D)
    h_g = jnp.einsum("egcd,edf->egcf", ex_in, p["w_gate"].astype(x.dtype))
    h_u = jnp.einsum("egcd,edf->egcf", ex_in, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(h_g) * h_u
    ex_out = jnp.einsum("egcf,efd->egcd", h, p["w_down"].astype(x.dtype))
    out = jnp.einsum("gsec,egcd->gsd", combine, ex_out).reshape(B, S, D)

    # aux losses (Switch-style load balance + router z-loss)
    frac_tokens = jnp.mean(onehot.sum(2), axis=(0, 1))          # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))                   # (E,)
    lb = m.n_experts * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_load_balance": m.router_aux_coef * lb,
           "moe_z_loss": m.router_z_coef * z}
    if "shared" in p:
        out = out + mlp_forward(p["shared"], x, cfg)
    return out, aux
