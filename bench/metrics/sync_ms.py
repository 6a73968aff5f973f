"""Device time of the sync program per sync (replica mean and S_k: the
Pallas kernel on one chip, the cross-chip pmean on a mesh), averaged over
the chips.  Nothing to read in a window without a sync.  Moves
tokens_per_s."""


def read(run, red):
    n = red["dispatches"].get("sync", 0)
    if not n:
        return None
    ns = [d["module_ns"].get("sync", 0.0) for d in red["devices"]]
    return sum(ns) / len(ns) / n / 1e6
