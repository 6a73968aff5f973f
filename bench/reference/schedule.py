"""ADPSGD's period controller, written from Algorithm 2 of the paper
(arXiv:2007.06134), for the reference and for replaying the trainer's
decisions.

The first ``warmup_full_sync_steps`` iterations each end in a sync.  Until
iteration ``k_sample`` the period stays at ``p_init`` and each sync adds
S_k / lr to the running mean C2.  After it, a sync whose S_k is below
``lower``·lr·C2 lengthens the period by one, and one above ``upper``·lr·C2
shortens it (within [p_min, p_max])."""
from __future__ import annotations


class Algorithm2:
    def __init__(self, a: dict, k_sample: int):
        self.a = a
        self.k_sample = k_sample
        self.p = a["p_init"]
        self.c2 = 0.0
        self.n_c2 = 0
        self.cnt = 0

    def sync_now(self, k: int) -> bool:
        if k < self.a["warmup_full_sync_steps"]:
            return True
        self.cnt += 1
        if self.cnt >= self.p:
            self.cnt = 0
            return True
        return False

    def observe(self, k: int, lr: float, s_k: float) -> None:
        a = self.a
        if k < a["warmup_full_sync_steps"]:
            return
        if k < self.k_sample:
            self.n_c2 += 1
            self.c2 += (s_k / lr - self.c2) / self.n_c2
            return
        if self.n_c2 == 0:
            self.n_c2, self.c2 = 1, s_k / lr
            return
        if s_k < a["lower"] * lr * self.c2:
            self.p = min(self.p + 1, a["p_max"])
        elif s_k > a["upper"] * lr * self.c2:
            self.p = max(self.p - 1, a["p_min"])


def replay(a: dict, k_sample: int, lr: float, n_steps: int, sync_steps,
           s_k, periods) -> int:
    """Feed the trainer's own S_k readings through Algorithm 2 over its
    first ``n_steps`` iterations; returns how many of its syncs (step or
    period after the sync) disagree, counting a missing or extra sync as
    one."""
    ctl = Algorithm2(a, k_sample)
    want = []
    for k in range(n_steps):
        if ctl.sync_now(k):
            if len(want) >= len(s_k):
                want.append((k, None))
                break
            ctl.observe(k, lr, float(s_k[len(want)]))
            want.append((k, ctl.p))
    got = list(zip(sync_steps, periods))
    n = min(len(got), len(want))
    return (sum(g != w for g, w in zip(got[:n], want[:n]))
            + abs(len(got) - len(want)))
