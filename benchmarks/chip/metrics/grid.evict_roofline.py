"""Share of its roofline that the victim-selection kernel reaches: the least
time over the kernel's measured device time. The least time is the larger
of its bytes bound (operand and result bytes of each call, as the compiled
instruction states them, over HBM bandwidth) and its operations bound; the
kernel issues no matrix-unit operations, so the bytes bound applies."""


def read(run):
    ks = [k for name, k in run.trace.kernels.items() if "evict" in name]
    ns = sum(k["ns"] for k in ks)
    if not ns:
        return None
    least_s = sum(k["bytes"] for k in ks) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
