"""``slab_ragged_gemm``: the routed experts' SwiGLU read in place from the
device slab, one launch per projection (gate, up, down).

For one MoE layer-step with ``experts`` distinct experts and ``pairs``
routed (token, expert) pairs, the algorithm needs every selected expert's
three ``d x f`` bfloat16 matrices read once, each pair's input row read by
the gate and up projections and its hidden row by the down projection, and
the three outputs written (bfloat16 gate/up, float32 down): not the padded
tiles the kernel reads.
"""

BF16, F32 = 2, 4


def flops(pairs: int, d: int, f: int) -> int:
    return 2 * pairs * d * f * 3


def nbytes(experts: int, pairs: int, d: int, f: int) -> int:
    weights = experts * 3 * d * f * BF16
    reads = pairs * (2 * d + f) * BF16
    writes = pairs * (2 * f * BF16 + d * F32)
    return weights + reads + writes
