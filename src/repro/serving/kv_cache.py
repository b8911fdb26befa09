"""KV-cache utilities: paged per-request KV state, growth, memory accounting.

Two allocation models live here:

* :class:`KVPagePool` — the continuous-batching allocator (DESIGN.md §4).
  One fixed-size pool of KV *pages* (``page_size`` token slots each) shared
  by every active request: ``alloc`` reserves a request's whole page budget
  at admission (reservation == allocation, so a mid-flight request can
  never deadlock on pages), ``gather`` materialises the active batch's
  ``[B, T, ...]`` cache views for one decode step, ``commit`` scatters each
  row's NEW token back to its (page, offset), and ``free`` returns the
  pages at retirement.  Pages are never zeroed on reuse: every consumer
  masks positions ``> pos`` to exactly-zero attention weight, so stale
  bytes are unobservable (the differential harness in
  tests/test_continuous_batching.py pins this bit-for-bit).
* :func:`grow_cache` — the legacy whole-cache copy used by the epoch-style
  (static batch) path and kept as the reference the page pool is validated
  against (tests/test_continuous_batching.py::test_page_pool_vs_grow_cache).

Sequence-dim leaves (the ``kv`` sub-tree: GQA k/v, MLA ckv/k_rope) are
paged on their token axis; sequence-free leaves (``ssm`` state, cross-attn
``xkv``) get one per-request *slot* in a ``[max_slots, ...]`` buffer,
rewritten wholesale each step.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import init_cache


def grow_cache(cfg, cache, batch: int, new_len: int):
    """Copy `cache` (prefill output, seq length S) into buffers of `new_len`.

    Sequence-length-free leaves (SSM states, cross-attn KV) pass through.
    """
    target = init_cache(cfg, batch, new_len)

    def merge(dst, src):
        if dst.shape != src.shape:
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), (0,) * dst.ndim)
        return src.astype(dst.dtype)

    return jax.tree.map(merge, target, cache)


def cache_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def unstack_layers(params_or_cache, cfg):
    """Stacked decoder tree -> flat per-layer list (python-loop serving)."""
    from repro.models.transformer import stack_layout
    prefix, period, m = stack_layout(cfg)
    tree = params_or_cache
    out = list(tree["prefix"])
    if tree.get("stack") is not None:
        for b in range(m):
            for j in range(period):
                out.append(jax.tree.map(lambda x: x[b], tree["stack"][f"sub_{j}"]))
    return out


def restack_layers(layers, cfg, template):
    """Inverse of unstack_layers (used to write back updated caches)."""
    from repro.models.transformer import stack_layout
    prefix, period, m = stack_layout(cfg)
    n_pre = len(prefix)
    out = {"prefix": list(layers[:n_pre]), "stack": None}
    if template.get("stack") is not None:
        blocks = {}
        for j in range(period):
            per_block = [layers[n_pre + b * period + j] for b in range(m)]
            blocks[f"sub_{j}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *per_block)
        out["stack"] = blocks
    return out


# ----------------------------------------------------------------------------
# paged KV pool (continuous batching)
# ----------------------------------------------------------------------------
class KVPagePool:
    """Fixed-size KV page pool shared by all active requests.

    Per layer, sequence leaves live in ``[n_pages, page_size, ...]``
    buffers addressed through per-request page tables; sequence-free
    leaves live in ``[max_slots, ...]`` buffers addressed by a per-request
    slot id.  All bookkeeping (free lists, tables) is host-side python —
    the pool is single-mutator like the expert caches: only the decode
    thread calls ``alloc``/``gather``/``commit``/``free``.
    """

    def __init__(self, cfg, *, page_size: int = 16, n_pages: int = 64,
                 max_slots: int = 8):
        from repro.models.transformer import init_layer_cache
        assert page_size >= 1 and n_pages >= 1 and max_slots >= 1
        self.cfg = cfg
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.max_slots = int(max_slots)
        # per-layer buffer trees, split by allocation model
        self._paged: List[Dict] = []     # {"kv": tree of [n_pages, page, ...]}
        self._slot: List[Dict] = []      # {"ssm"/"xkv": tree of [slots, ...]}
        for idx in range(cfg.n_layers):
            tpl = init_layer_cache(cfg, idx, 1, self.page_size)
            paged, slot = {}, {}
            for key, sub in tpl.items():
                if key == "kv":          # leaves [1, page_size, ...tail]
                    paged[key] = jax.tree.map(
                        lambda x: jnp.zeros((self.n_pages,) + x.shape[1:],
                                            x.dtype), sub)
                else:                    # leaves [1, ...tail] (seq-free)
                    slot[key] = jax.tree.map(
                        lambda x: jnp.zeros((self.max_slots,) + x.shape[1:],
                                            x.dtype), sub)
            self._paged.append(paged)
            self._slot.append(slot)
        self._free_pages: List[int] = list(range(self.n_pages))
        self._free_slots: List[int] = list(range(self.max_slots))
        self._tables: Dict[int, List[int]] = {}    # rid -> page ids
        self._slots: Dict[int, int] = {}           # rid -> slot id
        self._cap: Dict[int, int] = {}             # rid -> token capacity
        # bytes of the step views gather() has materialised, all steps
        self.gather_bytes = 0

    # -- accounting ------------------------------------------------------
    @property
    def n_used_pages(self) -> int:
        return self.n_pages - len(self._free_pages)

    @property
    def n_used_slots(self) -> int:
        return self.max_slots - len(self._free_slots)

    def page_nbytes(self) -> int:
        """Bytes one page holds across all layers' sequence leaves."""
        return sum(x.size // self.n_pages * x.dtype.itemsize
                   for lp in self._paged for x in jax.tree.leaves(lp))

    def slot_nbytes(self) -> int:
        """Bytes one request slot holds across all layers' seq-free leaves."""
        return sum(x.size // self.max_slots * x.dtype.itemsize
                   for ls in self._slot for x in jax.tree.leaves(ls))

    def used_bytes(self) -> int:
        """Bytes held by live (allocated) pages + slots — must return to 0
        once every request has retired (leak tripwire)."""
        return (self.n_used_pages * self.page_nbytes()
                + self.n_used_slots * self.slot_nbytes())

    def pool_bytes(self) -> int:
        """Total bytes of the backing buffers (fixed at construction)."""
        return (self.n_pages * self.page_nbytes()
                + self.max_slots * self.slot_nbytes())

    def summary(self) -> Dict[str, float]:
        return {"page_size": self.page_size, "n_pages": self.n_pages,
                "used_pages": self.n_used_pages,
                "used_slots": self.n_used_slots,
                "used_bytes": self.used_bytes(),
                "pool_bytes": self.pool_bytes(),
                "n_requests": len(self._tables)}

    # -- allocation ------------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    def alloc(self, rid: int, n_tokens: int):
        """Reserve `rid`'s full page budget (prompt + max new tokens) at
        admission.  All-or-nothing: a request that cannot get its whole
        allocation is not admitted, so active requests never stall on
        pages mid-flight."""
        assert rid not in self._tables, f"rid {rid} already allocated"
        need = self.pages_for(n_tokens)
        if need > len(self._free_pages) or not self._free_slots:
            raise RuntimeError(
                f"KV page pool exhausted: rid {rid} needs {need} pages "
                f"({len(self._free_pages)} free) and a slot "
                f"({len(self._free_slots)} free)")
        self._tables[rid] = [self._free_pages.pop() for _ in range(need)]
        self._slots[rid] = self._free_slots.pop()
        self._cap[rid] = need * self.page_size

    def free(self, rid: int):
        """Return `rid`'s pages + slot (retirement).  Contents are NOT
        zeroed — the next owner's masking makes them unobservable."""
        self._free_pages.extend(self._tables.pop(rid))
        self._free_slots.append(self._slots.pop(rid))
        self._cap.pop(rid)

    def capacity(self, rid: int) -> int:
        return self._cap[rid]

    # -- step views ------------------------------------------------------
    def gather(self, rids: Sequence[int]) -> List[Dict]:
        """Batched per-layer cache views for one decode step over `rids`:
        each sequence leaf becomes ``[B, T_pad, ...]`` (``T_pad`` = the
        longest active allocation, page-aligned; short rows pad with their
        own first page — masked, so contents are irrelevant), each
        seq-free leaf ``[B, ...]``.  The views have exactly the structure
        ``models.transformer.init_layer_cache`` produces, so the decode
        path consumes them unchanged."""
        B = len(rids)
        P = max(len(self._tables[r]) for r in rids)
        tables = np.stack([
            np.asarray(self._tables[r] +
                       [self._tables[r][0]] * (P - len(self._tables[r])),
                       np.int32)
            for r in rids])
        tab = jnp.asarray(tables)                              # [B, P]
        slots = jnp.asarray([self._slots[r] for r in rids], jnp.int32)
        out: List[Dict] = []
        for paged, slot in zip(self._paged, self._slot):
            view: Dict = {}
            for key, sub in paged.items():
                view[key] = jax.tree.map(
                    lambda x: x[tab].reshape(
                        (B, P * self.page_size) + x.shape[2:]), sub)
            for key, sub in slot.items():
                view[key] = jax.tree.map(lambda x: x[slots], sub)
            out.append(view)
        # shapes only: no wait on the device
        self.gather_bytes += sum(x.size * x.dtype.itemsize
                                 for x in jax.tree.leaves(out))
        return out

    def commit(self, caches: Sequence[Dict], rids: Sequence[int], positions):
        """Write each row's NEW token back from the step's updated views:
        sequence leaves scatter row ``b``'s ``positions[b]`` entry to its
        (page, offset); seq-free leaves rewrite the whole slot.  Raises if
        a row would write past its allocated capacity (the max_len guard
        the server relies on)."""
        positions = np.asarray(positions, np.int64)
        for r, pos in zip(rids, positions):
            if pos >= self._cap[r]:
                raise ValueError(
                    f"rid {r}: position {pos} >= allocated capacity "
                    f"{self._cap[r]} (page budget overflow)")
        pages = jnp.asarray([self._tables[r][int(p) // self.page_size]
                             for r, p in zip(rids, positions)], jnp.int32)
        offs = jnp.asarray(positions % self.page_size, jnp.int32)
        posv = jnp.asarray(positions, jnp.int32)
        rows = jnp.arange(len(rids))
        slots = jnp.asarray([self._slots[r] for r in rids], jnp.int32)
        for li, view in enumerate(caches):
            paged, slot = self._paged[li], self._slot[li]
            for key, sub in paged.items():
                paged[key] = jax.tree.map(
                    lambda buf, leaf: buf.at[pages, offs].set(
                        leaf[rows, posv]), sub, view[key])
            for key, sub in slot.items():
                slot[key] = jax.tree.map(
                    lambda buf, leaf: buf.at[slots].set(leaf), sub, view[key])
