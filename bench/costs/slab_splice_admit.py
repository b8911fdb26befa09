"""``slab_splice_admit``: one expert tensor's exponent and sign-mantissa
u8 planes spliced into bfloat16 and written into its slab slot.  Per
element the algorithm reads two bytes and writes two: 4 bytes, no FLOPs
worth counting."""


def nbytes(writes: int, elems: int) -> int:
    return 4 * writes * elems
