"""Fetch/cache (``core/engine.py``): the decode thread's blocked fetch
time over the window, ``fetch_wait_s + blocking_s`` of
``ZipServer.overlap_stats``, per step."""


def read(run):
    if not run.n_steps:
        return None
    d = sum(run.c1["overlap"][k] - run.c0["overlap"][k]
            for k in ("fetch_wait_s", "blocking_s"))
    return 1e3 * d / run.n_steps
