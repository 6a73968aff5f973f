"""Share of its roofline that the fused mean + squared-deviation kernel
(``kernels/param_variance.py``, op ``mean_and_sqdev``) reaches: the HBM
bytes of its operands and results, from their shapes and memory spaces in
the trace, over the chip's HBM bandwidth, against the kernel's summed
device time.  The kernel does 3 flops per element it reads, so bytes bound
it.  Nothing to read where the sync runs without the kernel.  Moves
tokens_per_s."""


def read(run, red):
    calls = [k for d in red["devices"] for k in d["kernels"]
             if k[0] == "mean_and_sqdev"]
    ns = sum(k[1] for k in calls)
    if not calls or ns <= 0:
        return None
    least_s = sum(k[2] for k in calls) / run["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
