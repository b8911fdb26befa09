"""``recover_bf16``: one expert tensor's exponent and sign-mantissa u8
planes spliced into a standalone bfloat16 tensor (a miss that no slab slot
takes).  Per element the algorithm reads two bytes and writes two: 4
bytes, no FLOPs worth counting."""


def nbytes(splices: int, elems: int) -> int:
    return 4 * splices * elems
