"""Kernels (``kernels/moe_gemm.py``): least time of the window's
``slab_ragged_gemm`` work, at the bytes and FLOPs the algorithm needs
(``bench/costs/slab_ragged_gemm.py``, from the window's routed pairs and
the distinct experts of each layer-step), over the kernel's device time in
the trace, in percent."""
from bench.costs import least_seconds
from bench.costs import slab_ragged_gemm as cost

KERNEL = ("slab_ragged_gemm", "_slab_gemm_tpu", "_slab_gemm_kernel")


def read(run):
    if not run.peaks:
        return None
    if run.trace is None or not run.trace["kernel_s"].get("ragged_gemm_roofline"):
        return None
    d, f = run.d_model, run.d_expert
    pairs = run.c1["overlap"]["tokens_real"] - run.c0["overlap"]["tokens_real"]
    experts = sum(s["n_experts"] for s in run.layer_steps)
    least = least_seconds(cost.flops(pairs, d, f),
                          cost.nbytes(experts, pairs, d, f), run.peaks)
    return 100.0 * least / run.trace["kernel_s"]["ragged_gemm_roofline"]
