"""Share of the traced window in which no op ran on the device, averaged
over the chips.  Moves tokens_per_s."""


def read(run, red):
    w0, w1 = red["window_ns"]
    busy = sum(d["busy_ns"] for d in red["devices"]) / len(red["devices"])
    return 100.0 * (1.0 - busy / (w1 - w0))
