"""Batching layer (``serving/server.py``): median over the window's steps
of the loop period minus the time inside ``decode_rows`` -- KV gather and
commit, admission, per-row sampling syncs."""
from statistics import median


def read(run):
    s = run.steps
    if len(s) < 2:
        return None
    return 1e3 * median((b.t_call - a.t_call) - (a.t_ready - a.t_call)
                        for a, b in zip(s, s[1:]))
