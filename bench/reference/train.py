"""The first iterations of local SGD, in plain float32: each replica takes
AdamW steps on its own batch, and at each sync that Algorithm 2 schedules
the replicas are replaced by their mean and S_k = (1/R)·Σ_r ||mean − w_r||²
is recorded.  Replica r lives on device r mod n, so a cell on four chips
holds one replica per chip, and a sync gathers one leaf at a time.

``fault`` plants one of the faults a run must catch, in this reference put
in the program's place: ``half`` scores only the first half of each
sequence, ``no_exchange`` skips the replacement by the mean (S_k is still
measured)."""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference.schedule import Algorithm2


def leaf_norms(tree) -> jnp.ndarray:
    """Euclidean norm of every leaf, in leaf order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def make_step(nll_sum: Callable, opt: dict, nx):
    """One replica's mean loss, gradient leaf norms and AdamW update.  The
    loss is summed one sequence at a time, each recomputed in the backward
    pass, so a batch of long sequences fits."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    row = jax.checkpoint(nll_sum)

    def loss_fn(p, tokens, mask):
        total = sum(row(p, tokens[b:b + 1], mask[b:b + 1])
                    for b in range(tokens.shape[0]))
        return total / jnp.sum(mask)

    def step(p, m, v, tokens, mask, lr, t):
        loss, g = jax.value_and_grad(loss_fn)(p, tokens, mask)
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree_util.tree_map(
            lambda v_, g_: b2 * v_ + (1 - b2) * jnp.square(g_), v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                                          + wd * p_), p, m, v)
        p, m, v = nx.keep(p), nx.keep(m), nx.keep(v)
        return p, m, v, loss, leaf_norms(g)

    donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
    return jax.jit(step, donate_argnums=donate)


def run(fam, c: dict, a: dict, k_sample: int, seed: int, batches: List,
        nx, fault: Optional[str] = None) -> dict:
    """Train from the seed's weights through ``len(batches)`` iterations.
    ``batches[k]`` is the (R, B, S) token array the program was fed at
    iteration k.  Returns per-iteration mean losses, the first gradient's
    leaf norms (L, R), S_k at each sync, and each replica's parameter
    change leaf norms (L, R) after the last iteration."""
    devs = jax.devices()
    R = batches[0].shape[0]
    opt, lr = c["optimizer"], float(c["optimizer"]["lr"])
    init = jax.jit(lambda key: fam.init_params(key, c))
    key = jax.random.PRNGKey(seed)
    step = make_step(
        lambda p, tok, mask: fam.nll_sum(p, tok, c, nx, mask), opt, nx)
    P, M, V = [], [], []
    for r in range(R):
        dev = devs[r % len(devs)]
        P.append(jax.device_put(nx.keep(init(key)), dev))
        M.append(jax.tree_util.tree_map(jnp.zeros_like, P[r]))
        V.append(jax.tree_util.tree_map(jnp.zeros_like, P[r]))
    ctl = Algorithm2(a, k_sample)
    out = {"losses": [], "s_k": [], "sync_steps": []}
    for k, tokens in enumerate(batches):
        losses = []
        for r in range(R):
            dev = devs[r % len(devs)]
            tok = jax.device_put(jnp.asarray(tokens[r]), dev)
            mask = np.ones((tok.shape[0], tok.shape[1] - 1), np.float32)
            if fault == "half":
                mask[:, mask.shape[1] // 2:] = 0.0
            P[r], M[r], V[r], loss, gn = step(
                P[r], M[r], V[r], tok, jax.device_put(mask, dev),
                lr, float(k + 1))
            losses.append((loss, gn))      # read once every replica runs
        if k == 0:
            out["grad_norms"] = [np.asarray(gn) for _, gn in losses]
        out["losses"].append(float(np.mean([float(v) for v, _ in losses])))
        if ctl.sync_now(k):
            s_k = _sync(P, devs, apply=fault != "no_exchange")
            ctl.observe(k, lr, s_k)
            out["s_k"].append(s_k)
            out["sync_steps"].append(k)
    del M, V
    W0 = init(key)
    out["grad_norms"] = np.stack(out["grad_norms"], axis=1)
    out["update_norms"] = np.stack(
        [np.asarray(_delta_norms(P[r], jax.device_put(W0, _device(P[r]))))
         for r in range(R)], axis=1)
    return out


def _device(tree):
    return next(iter(jax.tree_util.tree_leaves(tree)[0].devices()))


@jax.jit
def _delta_norms(p, p0):
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0))


def _sync(P, devs, apply: bool) -> float:
    """Replace every replica by the replica mean (gathered on the first
    replica's chip); return S_k."""
    R = len(P)
    home = [_device(p) for p in P]
    gathered = [jax.device_put(p, home[0]) for p in P]
    mean, s_k = _mean_sk(gathered)
    del gathered
    if apply:
        for r in range(R):
            # each replica gets a buffer of its own: the step donates them
            P[r] = jax.device_put(mean, home[r]) if home[r] != home[0] \
                else jax.tree_util.tree_map(jnp.copy, mean)
    return float(s_k)


@jax.jit
def _mean_sk(trees):
    R = len(trees)
    mean = jax.tree_util.tree_map(lambda *xs: sum(xs) / R, *trees)
    s_k = sum(jnp.sum(jnp.square(m - x))
              for t in trees
              for m, x in zip(jax.tree_util.tree_leaves(mean),
                              jax.tree_util.tree_leaves(t))) / R
    return mean, s_k
