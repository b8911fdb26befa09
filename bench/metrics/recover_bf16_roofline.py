"""Kernels: least time of the window's standalone ``recover_bf16`` splices
(the engine's ``splice_ops``, 4 bytes per element of each tensor,
``bench/costs``) over the device time of the program that runs the kernel
(``jit_recover_bf16``: the kernel and the reshapes of its planes), in
percent."""
from bench.costs import least_seconds
from bench.costs import recover_bf16 as cost

KERNEL = ("recover_bf16",)


def read(run):
    if not run.peaks:
        return None
    if run.trace is None or not run.trace["kernel_s"].get("recover_bf16_roofline"):
        return None
    splices = run.c1["splices"] - run.c0["splices"]
    elems = run.d_model * run.d_expert
    return 100.0 * least_seconds(0, cost.nbytes(splices, elems), run.peaks) \
        / run.trace["kernel_s"]["recover_bf16_roofline"]
