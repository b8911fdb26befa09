"""Operations and bytes the algorithms need, computed from shapes.

One module per Pallas kernel, plus ``model`` for the FLOPs of one decoded
token.  A roofline share is the least time the chip could take -- the
larger of operations over peak FLOP/s and bytes over peak bandwidth --
over the kernel's measured device time.
"""


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
