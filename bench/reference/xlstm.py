"""Plain float32 reference of xLSTM (arXiv:2405.04517) as the trainer builds
it: pre-LayerNorm residual blocks, each an mLSTM or an sLSTM block with no
separate feed-forward sublayer, the embedding tied to the output head.

mLSTM block (pre up-projection by 2): x -> [xi, z]; a causal depthwise
convolution of width 4 and SiLU give c; q = c·Wq/sqrt(dh), k = c·Wk,
v = xi·Wv; exponential input gate log i = c·Wi + b_i and sigmoid forget
gate log f = log σ(c·Wf + b_f), per head.  The memory is read in the
paper's parallel form: with F_t = Σ_{r≤t} log f_r and
D_ts = F_t − F_s + log i_s (s ≤ t), m_t = max_s D_ts,
h_t = Σ_s (q_t·k_s) e^{D_ts−m_t} v_s / max(|Σ_s (q_t·k_s) e^{D_ts−m_t}|, e^{−m_t}).
Then a per-head group norm, the output gate SiLU(z), and the down
projection.

sLSTM block (post up-projection): gates from x·Wx plus a per-head
recurrent product of h_{t−1}, exponential input and sigmoid forget gates
with the stabilizer m, c_t = f'c + i'z, n_t = f'n + i', h_t = o·c_t/max(n_t, 1),
a per-head group norm, then a gated feed-forward of width 4/3·D rounded
down to a multiple of 64.

Departures from the paper, shared with the trainer: dense (not
block-diagonal) q/k/v projections, no convolution before the sLSTM gates,
no learnable skip in the mLSTM block.  Nothing here imports the program."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
GN_EPS = 1e-5
CONV = 4


def sizes(c: dict) -> dict:
    D, H = c["embedding_dim"], c["num_heads"]
    return dict(L=c["num_blocks"], D=D, H=H, Di=int(c["mlstm_proj_factor"] * D),
                V=c["vocab_size"], slstm_at=tuple(c["slstm_at"]),
                ff=int(D * c["slstm_ff_proj_factor"] / 64) * 64)


def program_config(c: dict):
    s = sizes(c)
    pattern = tuple("slstm" if i in s["slstm_at"] else "mlstm"
                    for i in range(s["L"]))
    if not c["tie_word_embeddings"]:
        raise ValueError("the xLSTM reference covers tied embeddings only")
    return c["program_arch"], dict(
        n_layers=s["L"], d_model=s["D"], n_heads=s["H"], n_kv_heads=s["H"],
        d_head=0, d_ff=0, vocab_size=s["V"], norm_type="layernorm",
        norm_eps=LN_EPS, pos_type="none", layer_pattern=pattern,
        tie_embeddings=True, vocab_pad_multiple=1,
        param_dtype=c["dtypes"]["params"],
        compute_dtype=c["dtypes"]["compute"])


def init_params(key, c: dict) -> dict:
    """Seeded float32 weights in the trainer's layout (one replica)."""
    s = sizes(c)
    D, H, Di, V, ff = s["D"], s["H"], s["Di"], s["V"], s["ff"]
    dh_s = D // H
    n = [0]

    def normal(shape, std):
        n[0] += 1
        return jax.random.normal(jax.random.fold_in(key, n[0]), shape,
                                 jnp.float32) * std

    def ln():
        return {"scale": jnp.ones((D,)), "bias": jnp.zeros((D,))}

    blocks = []
    for i in range(s["L"]):
        if i in s["slstm_at"]:
            blocks.append({"norm1": ln(), "slstm": {
                "wx": normal((D, 4 * D), 1 / math.sqrt(D)),
                "r": normal((H, dh_s, 4 * dh_s), 1 / math.sqrt(dh_s)),
                "b": jnp.concatenate([jnp.zeros((D,)), jnp.full((D,), 3.0),
                                      jnp.zeros((2 * D,))]),
                "gn": jnp.ones((D,)),
                "ff_gate": normal((D, ff), 1 / math.sqrt(D)),
                "ff_up": normal((D, ff), 1 / math.sqrt(D)),
                "ff_down": normal((ff, D), 1 / math.sqrt(ff)),
            }})
        else:
            blocks.append({"norm1": ln(), "mlstm": {
                "up": normal((D, 2 * Di), 1 / math.sqrt(D)),
                "conv_w": normal((CONV, Di), 1 / math.sqrt(CONV)),
                "conv_b": jnp.zeros((Di,)),
                "wq": normal((Di, Di), 1 / math.sqrt(Di)),
                "wk": normal((Di, Di), 1 / math.sqrt(Di)),
                "wv": normal((Di, Di), 1 / math.sqrt(Di)),
                "w_if": normal((Di, 2 * H), 1 / math.sqrt(Di)),
                "b_i": jnp.zeros((H,)),
                "b_f": jnp.full((H,), 3.0),
                "ogate_norm": jnp.ones((Di,)),
                "down": normal((Di, D), 1 / math.sqrt(Di)),
            }})
    return {"embed": normal((V, D), 0.02), "final_norm": ln(),
            "blocks": blocks}


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _group_norm(h, scale):
    """h: (B, S, H, dh) normalized per head, then (B, S, H·dh) · scale."""
    mean = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mean), axis=-1, keepdims=True)
    y = (h - mean) * jax.lax.rsqrt(var + GN_EPS)
    return y.reshape(h.shape[0], h.shape[1], -1) * scale


def _mlstm(p, x, s, nx):
    B, S, _ = x.shape
    H, Di = s["H"], s["Di"]
    dh = Di // H
    up = nx.einsum("bsd,de->bse", x, p["up"])
    xi, z = up[..., :Di], up[..., Di:]
    xp = jnp.concatenate([jnp.zeros((B, CONV - 1, Di)), xi], axis=1)
    conv = sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(CONV))
    cx = jax.nn.silu(conv + p["conv_b"])
    q = nx.einsum("bsd,de->bse", cx, p["wq"]).reshape(B, S, H, dh)
    q = q / math.sqrt(dh)
    k = nx.einsum("bsd,de->bse", cx, p["wk"]).reshape(B, S, H, dh)
    v = nx.einsum("bsd,de->bse", xi, p["wv"]).reshape(B, S, H, dh)
    gates = nx.einsum("bsd,dg->bsg", cx, p["w_if"])
    log_i = gates[..., :H] + p["b_i"]
    log_f = jax.nn.log_sigmoid(gates[..., H:] + p["b_f"])
    F = jnp.cumsum(log_f, axis=1)                            # (B, S, H)
    Dm = (F[:, :, None, :] - F[:, None, :, :]
          + log_i[:, None, :, :])                            # (B, t, s, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    Dm = jnp.where(causal, Dm, -1e30)
    m = jnp.max(Dm, axis=2)                                  # (B, t, H)
    w = jnp.exp(Dm - m[:, :, None, :])
    scores = nx.einsum("bthd,bshd->btsh", q, k) * w
    num = nx.einsum("btsh,bshd->bthd", scores, v)
    den = jnp.sum(scores, axis=2)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m))[..., None]
    h = _group_norm(h, p["ogate_norm"]) * jax.nn.silu(z)
    return nx.einsum("bse,ed->bsd", h, p["down"])


def _slstm(p, x, s, nx):
    B, S, D = x.shape
    H = s["H"]
    dh = D // H
    xg = nx.einsum("bsd,de->bse", x, p["wx"])

    def cell(st, xt):
        c, n, h, m = st
        rec = nx.einsum("bhd,hde->bhe", h.reshape(B, H, dh),
                        p["r"]).reshape(B, 4 * D)
        pre = xt + rec + p["b"]
        li, lf, zz, oo = jnp.split(pre, 4, axis=-1)
        log_f = jax.nn.log_sigmoid(lf)
        m_new = jnp.maximum(log_f + m, li)
        fs, is_ = jnp.exp(log_f + m - m_new), jnp.exp(li - m_new)
        c = fs * c + is_ * jnp.tanh(zz)
        n = fs * n + is_
        h = jax.nn.sigmoid(oo) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    zero = jnp.zeros((B, D))
    _, hs = jax.lax.scan(cell, (zero, zero, zero, jnp.full((B, D), -1e30)),
                         xg.swapaxes(0, 1))
    h = _group_norm(hs.swapaxes(0, 1).reshape(B, S, H, dh), p["gn"])
    g = jax.nn.silu(nx.einsum("bsd,df->bsf", h, p["ff_gate"]))
    u = nx.einsum("bsd,df->bsf", h, p["ff_up"])
    return nx.einsum("bsf,fd->bsd", g * u, p["ff_down"])


def nll_sum(params: dict, tokens, c: dict, nx, mask):
    """Summed next-token cross-entropy of a (B, S) batch, weighted by
    ``mask`` (B, S-1)."""
    s = sizes(c)
    x = params["embed"][tokens]
    for p in params["blocks"]:
        if "mlstm" in p:
            x = x + _mlstm(p["mlstm"], _ln(x, p["norm1"]), s, nx)
        else:
            x = x + _slstm(p["slstm"], _ln(x, p["norm1"]), s, nx)
    logits = nx.einsum("bsd,vd->bsv", _ln(x, params["final_norm"]),
                       params["embed"])[:, :-1]
    tgt = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0])
    return jnp.sum(nll * mask)


def flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward, recompute
    excluded: 6 per weight that multiplies the token (projections, the
    depthwise convolution, the sLSTM recurrent matrices, the tied head; not
    the embedding lookup), plus per mLSTM block 3·(4·C·Di + 4·dh·Di) for
    the trainer's chunkwise form with chunks of C = 256 positions: scores
    and their weighted sum inside a chunk over the full C x C square, and
    the state's read-out and update."""
    s = sizes(c)
    D, H, Di, V, ff = s["D"], s["H"], s["Di"], s["V"], s["ff"]
    dh = Di // H
    chunk = min(256, seq)
    n_s = len(s["slstm_at"])
    n_m = s["L"] - n_s
    m_w = D * 2 * Di + CONV * Di + 3 * Di * Di + Di * 2 * H + Di * D
    s_w = D * 4 * D + H * (D // H) * 4 * (D // H) + 3 * D * ff
    weights = n_m * m_w + n_s * s_w + V * D
    return 6.0 * weights + n_m * 3.0 * (4 * chunk * Di + 4 * dh * Di)
