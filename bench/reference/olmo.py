"""Plain float32 reference of OLMo (arXiv:2402.00838) as the trainer lays
out its parameters: pre-norm decoder blocks with non-parametric LayerNorm,
rotary attention (half-split rotation), SwiGLU feed-forward, embedding tied
to the output head, next-token cross-entropy over positions 0..S-2.

Nothing here imports the program.  ``init_params`` makes the weights from
the seed for both the program and this reference."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def sizes(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dict(L=c["num_hidden_layers"], D=d, H=h, K=c["num_key_value_heads"],
                dh=d // h, F=c["intermediate_size"], V=c["vocab_size"],
                theta=float(c["rope_theta"]))


def program_config(c: dict):
    """(registered architecture, ModelConfig fields) that run ``c``."""
    if not c["tie_word_embeddings"] or c["attention_bias"]:
        raise ValueError("the OLMo reference covers tied embeddings and "
                         "bias-free attention only")
    s = sizes(c)
    return c["program_arch"], dict(
        n_layers=s["L"], d_model=s["D"], n_heads=s["H"], n_kv_heads=s["K"],
        d_head=0, d_ff=s["F"], vocab_size=s["V"], rope_theta=s["theta"],
        norm_type="nonparametric_ln", norm_eps=LN_EPS, mlp_type="swiglu",
        pos_type="rope", tie_embeddings=True, vocab_pad_multiple=1,
        param_dtype=c["dtypes"]["params"],
        compute_dtype=c["dtypes"]["compute"])


def init_params(key, c: dict) -> dict:
    """Seeded float32 weights in the trainer's layout (one replica)."""
    s = sizes(c)
    D, H, K, dh, F, V = s["D"], s["H"], s["K"], s["dh"], s["F"], s["V"]
    n = [0]

    def normal(shape, std):
        n[0] += 1
        return jax.random.normal(jax.random.fold_in(key, n[0]), shape,
                                 jnp.float32) * std

    def dense(d_in, d_out):
        return {"w": normal((d_in, d_out), 1.0 / math.sqrt(d_in))}

    blocks = []
    for _ in range(s["L"]):
        blocks.append({
            "norm1": {},
            "attn": {"wq": dense(D, H * dh), "wk": dense(D, K * dh),
                     "wv": dense(D, K * dh), "wo": dense(H * dh, D)},
            "norm2": {},
            "mlp": {"w_gate": dense(D, F), "w_up": dense(D, F),
                    "w_down": dense(F, D)},
        })
    return {"embed": normal((V, D), 0.02), "final_norm": {}, "blocks": blocks}


def _ln(x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS)


def _rope(x, theta):
    """x: (B, S, H, dh); rotate the two halves of each head."""
    S, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def nll_sum(params: dict, tokens, c: dict, nx, mask):
    """Summed next-token cross-entropy of a (B, S) batch, weighted by
    ``mask`` (B, S-1)."""
    s = sizes(c)
    H, K, dh = s["H"], s["K"], s["dh"]
    B, S = tokens.shape
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, p):
        h = _ln(x)
        a = p["attn"]
        q = nx.einsum("bsd,de->bse", h, a["wq"]["w"]).reshape(B, S, H, dh)
        k = nx.einsum("bsd,de->bse", h, a["wk"]["w"]).reshape(B, S, K, dh)
        v = nx.einsum("bsd,de->bse", h, a["wv"]["w"]).reshape(B, S, K, dh)
        q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
        logits = nx.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        w = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        o = nx.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * dh)
        x = x + nx.einsum("bse,ed->bsd", o, a["wo"]["w"])
        h = _ln(x)
        m = p["mlp"]
        g = jax.nn.silu(nx.einsum("bsd,df->bsf", h, m["w_gate"]["w"]))
        u = nx.einsum("bsd,df->bsf", h, m["w_up"]["w"])
        return x + nx.einsum("bsf,fd->bsd", g * u, m["w_down"]["w"])

    for p in params["blocks"]:
        x = jax.checkpoint(block)(x, p)
    logits = nx.einsum("bsd,vd->bsv", _ln(x), params["embed"])[:, :-1]
    tgt = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0])
    return jnp.sum(nll * mask)


def flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward, recompute
    excluded: 6 per weight that multiplies the token (the tied head
    included, the embedding lookup not) plus 12·L·D·S for the attention
    scores and their weighted sum over the full S x S square that the
    trainer computes."""
    s = sizes(c)
    D, H, K, dh, F = s["D"], s["H"], s["K"], s["dh"], s["F"]
    per_layer = D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F
    weights = s["L"] * per_layer + s["V"] * D
    return 6.0 * weights + 12.0 * s["L"] * H * dh * seq
