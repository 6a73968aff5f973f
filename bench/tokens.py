"""Token traffic of a training cell, made from the seed.

The same learnable process as the trainer's own synthetic corpus: each
sequence starts at a uniform token, and every next token is
(a·previous + c) mod V with probability 1 − eps, uniform otherwise.  A
cell's traffic file gives ``rows`` (sequences in the corpus, reshuffled
every epoch by the trainer's input pipeline), a, c and eps; the seed
changes which tokens, never how many."""
from __future__ import annotations

import numpy as np


def corpus(seed: int, vocab: int, seq: int, t: dict) -> np.ndarray:
    rows, a, c, eps = t["rows"], t["a"], t["c"], t["eps"]
    rng = np.random.default_rng(seed)
    toks = np.empty((rows, seq), np.int32)
    toks[:, 0] = rng.integers(0, vocab, rows)
    noise = rng.integers(0, vocab, (rows, seq))
    flip = rng.random((rows, seq)) < eps
    for i in range(1, seq):
        det = (a * toks[:, i - 1].astype(np.int64) + c) % vocab
        toks[:, i] = np.where(flip[:, i], noise[:, i], det)
    return toks
