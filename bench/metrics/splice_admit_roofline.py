"""Kernels: least time of the window's ``slab_splice_admit`` writes (4
bytes per element of each tensor written, ``bench/costs``) over the
kernel's device time in the trace, in percent."""
from bench.costs import least_seconds
from bench.costs import slab_splice_admit as cost

KERNEL = ("slab_splice_admit", "_splice_set_tpu", "_splice_admit_kernel")


def read(run):
    if not run.peaks:
        return None
    if run.trace is None or not run.trace["kernel_s"].get("splice_admit_roofline"):
        return None
    writes = run.c1["splice_admits"] - run.c0["splice_admits"]
    elems = run.d_model * run.d_expert
    return 100.0 * least_seconds(0, cost.nbytes(writes, elems), run.peaks) \
        / run.trace["kernel_s"]["splice_admit_roofline"]
