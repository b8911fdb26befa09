"""Decode step (``serving/zipserve.py``): median host time inside
``decode_rows`` up to its logits being ready, over the window's steps."""
from statistics import median


def read(run):
    if not run.steps:
        return None
    return 1e3 * median(s.t_ready - s.t_call for s in run.steps)
