"""Device time of the local-step program per step (model forward, backward
and AdamW), from the program runs that step dispatches launched in the
traced window, averaged over the chips.  Moves tokens_per_s."""


def read(run, red):
    n = red["dispatches"].get("step", 0)
    if not n:
        return None
    ns = [d["module_ns"].get("step", 0.0) for d in red["devices"]]
    return sum(ns) / len(ns) / n / 1e6
