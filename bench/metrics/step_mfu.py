"""Model step: tokens per second of the traced run times the model FLOPs
of a token at the window's mean context (``bench/costs/model.py``) over
the chip's peak, in percent."""
from bench.costs import model


def read(run):
    if not run.peaks:
        return None
    if not run.tokens_per_s:
        return None
    return 100.0 * run.tokens_per_s * model.flops_per_token(
        run.conf, run.mean_ctx) / run.peaks["bf16_flops"]
