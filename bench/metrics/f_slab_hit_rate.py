"""Fetch/cache (``core/cache.py``): the share of expert accesses in the
window that found the expert in the device slab (F pool), from
``cache_summary()`` hits and accesses."""


def read(run):
    acc = run.c1["accesses"] - run.c0["accesses"]
    if acc <= 0:
        return None
    return (run.c1["hits_F"] - run.c0["hits_F"]) / acc
