"""Device-resident expert slabs: preallocated stacked weight buffers.

The decode hot path historically paid a host-side staging tax the paper's
CUDA pipeline avoids: every step re-uploaded and re-stacked the active
experts' full bf16 weights from host numpy, even when every expert was an
F-pool cache hit.  A :class:`DeviceSlabCache` removes that tax — per MoE
layer it preallocates one device buffer of shape ``[capacity, *tensor_shape]``
per expert tensor name (capacity = the layer's F-pool size), and F-pool
residency maps experts to *slots* in those buffers:

* **write** — a freshly spliced tensor (already on device, see
  ``kernels/ops.recover_bf16_device``) lands in its slot via a *donated*
  ``.at[slot].set`` update: XLA reuses the slab buffer in place instead of
  copying ``capacity × bytes`` per admission.
* **gather** — the grouped FFN pulls the step's active experts with one
  ``jnp.take`` per tensor name: a device-side gather, zero host↔device
  traffic on a cache-hit step.
* **free/reuse** — slots carry a generation counter; freeing a slot bumps
  it, so a stale :class:`SlotRef` held by an in-flight speculative job can
  be detected (``ref.valid``) and is never re-admitted as if it still named
  the old expert's weights.

Thread model: all slab mutation happens on the engine caller's (decode)
thread — the same single-mutator discipline as the cache pools.  Worker
threads only produce the device tensors that are later written here.

Donation caveat (DESIGN.md §3.5): on backends without in-place donation
support XLA silently falls back to copy-on-write; correctness is unchanged,
only the write cost grows to O(capacity).  CPU jax ≥ 0.4.3x donates
in-place (the unit test asserts the old buffer is actually deleted).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import checkz
from repro.core.faults import PeerLinkError


@functools.partial(jax.jit, donate_argnums=(0,))
def _slab_set(buf: jnp.ndarray, slot: jnp.ndarray, val: jnp.ndarray
              ) -> jnp.ndarray:
    """Donated slot write: the old slab buffer is consumed in place."""
    return jax.lax.dynamic_update_index_in_dim(buf, val, slot, 0)


@functools.partial(jax.jit, static_argnames=())
def _slab_take(buf: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(buf, slots, axis=0)


@dataclass
class DevicePlanes:
    """One tensor held as its two ZipMoE bit-planes, ALREADY on device.

    The fused demand-miss path's in-flight form: the worker uploads the u8
    planes (charged to ``h2d_bytes``) but defers the splice; at collect
    time the decode thread lands them straight in a slab slot via the
    aliased splice-admit kernel (one launch — no standalone spliced tensor,
    no capacity-sized copy).  ``_sm_plane_of`` reads ``.sm`` for S-pool
    demotions exactly as it does for host-side BitPlanes."""
    exp: jnp.ndarray            # u8, device, flat
    sm: jnp.ndarray             # u8, device, flat
    shape: Tuple[int, ...]

    @property
    def nbytes(self) -> int:
        return int(self.exp.size) + int(self.sm.size)


@dataclass(frozen=True)
class SlotRef:
    """Handle to one tensor of one expert inside a slab.

    Cache payloads in ``device_cache`` mode carry these instead of
    ndarrays.  A ref is only as durable as its slot's generation: freeing
    the slot (F-pool eviction/demotion) bumps ``slab.gen[slot]`` and every
    outstanding ref for the old occupant turns invalid."""
    slab: "DeviceSlabCache"
    slot: int
    gen: int
    name: str

    @property
    def valid(self) -> bool:
        return self.slab.gen[self.slot] == self.gen

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.slab.shapes[self.name]

    def read(self) -> jnp.ndarray:
        """Device view of the slot's tensor (no host transfer)."""
        assert self.valid, f"stale SlotRef {self.name}@{self.slot}"
        return self.slab.bufs[self.name][self.slot]

    def read_np(self) -> np.ndarray:
        """One-time d2h download (used by F→S payload demotion)."""
        arr = np.asarray(self.read())
        self.slab.d2h_bytes += arr.nbytes
        return arr


class DeviceSlabCache:
    """Per-layer stacked device buffers backing the F pool's residents."""

    def __init__(self, layer: int, shapes: Dict[str, Tuple[int, ...]],
                 capacity: int, dtype=jnp.bfloat16):
        assert capacity > 0, capacity
        self.layer = layer
        self.capacity = int(capacity)
        self.shapes = {name: tuple(s) for name, s in shapes.items()}
        self.dtype = dtype
        self.bufs: Dict[str, jnp.ndarray] = {
            name: jnp.zeros((self.capacity,) + tuple(s), dtype)
            for name, s in self.shapes.items()}
        self.slot_of: Dict[int, int] = {}          # expert -> slot
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self.gen: List[int] = [0] * self.capacity
        self.writes = 0                             # slot-write count
        self.splice_writes = 0                      # of which fused admits
        self.splice_s = 0.0                         # fused-admit wall time
        self.d2h_bytes = 0                          # demotion downloads
        # no locks by design: all mutation on the engine caller's (decode)
        # thread; ZIPMOE_CHECK=1 asserts that (see checkz.MutatorGuard)
        self._guard = checkz.make_guard(f"DeviceSlabCache(layer={layer})")

    # -- queries -----------------------------------------------------------
    def __contains__(self, expert: int) -> bool:
        return expert in self.slot_of

    def refs(self, expert: int) -> Dict[str, SlotRef]:
        slot = self.slot_of[expert]
        g = self.gen[slot]
        return {name: SlotRef(self, slot, g, name) for name in self.shapes}

    def nbytes(self) -> int:
        return sum(int(b.size) * b.dtype.itemsize for b in self.bufs.values())

    # -- mutation (decode thread only) -------------------------------------
    def put(self, expert: int, tensors: Dict[str, jnp.ndarray]
            ) -> Dict[str, SlotRef]:
        """Write `tensors` (one per name) into the expert's slot —
        allocating one if needed — via donated in-place updates.  A value
        may be a plain device array (plain slot write) or a
        :class:`DevicePlanes` (fused splice-admit: the bit-plane splice and
        the slot write happen in ONE aliased kernel launch — the demand
        miss warms the slab without ever materializing a standalone spliced
        tensor).  Only the fused path is bit-exact on the TPU for subnormal
        and NaN-payload weights: a plain write is an XLA
        dynamic-update-slice, which on a v5e flushes and quiets them."""
        from repro.kernels import ops
        assert set(tensors) == set(self.shapes), (set(tensors),
                                                  set(self.shapes))
        self._guard.check()
        slot = self.slot_of.get(expert)
        if slot is None:
            assert self._free, f"slab full (capacity={self.capacity})"
            slot = self._free.pop()
            self.slot_of[expert] = slot
        idx = jnp.int32(slot)
        for name, val in tensors.items():
            if isinstance(val, DevicePlanes):
                assert tuple(val.shape) == self.shapes[name], (name,
                                                               val.shape)
                t0 = time.perf_counter()
                self.bufs[name] = ops.slab_splice_set(self.bufs[name], slot,
                                                      val.exp, val.sm)
                self.splice_s += time.perf_counter() - t0
                self.splice_writes += 1
                continue
            assert tuple(val.shape) == self.shapes[name], (name, val.shape)
            self.bufs[name] = _slab_set(self.bufs[name],
                                        idx, jnp.asarray(val, self.dtype))
        self.writes += 1
        return self.refs(expert)

    def free(self, expert: int):
        """Release the expert's slot; bumping the generation invalidates
        every outstanding SlotRef to the old occupant."""
        self._guard.check()
        slot = self.slot_of.pop(expert, None)
        if slot is None:
            return
        self.gen[slot] += 1
        self._free.append(slot)

    def retire(self):
        """Decommission the whole slab (live re-planning: the layer's F
        pool was re-sized — residents migrate to a fresh slab — or went
        cold and releases its device memory entirely).  Every slot's
        generation is bumped so ALL outstanding SlotRefs turn stale, and
        the buffers are dropped so XLA can reclaim the device memory once
        the last reference dies; a read through a stale ref trips the
        usual validity assertion instead of returning zombie bytes."""
        self._guard.check()
        for slot in range(self.capacity):
            self.gen[slot] += 1
        self.slot_of.clear()
        self._free = list(range(self.capacity - 1, -1, -1))
        self.bufs = {}

    # -- the hot-path read -------------------------------------------------
    def gather(self, name: str, slots: Sequence[int]) -> jnp.ndarray:  # hot-path
        """``[len(slots), *shape]`` device gather — a MATERIALIZED copy of
        the active experts (the pre-megakernel staging step; callers charge
        it to ``w_copy_bytes``).  The slot-indexed ragged GEMM reads
        ``self.bufs[name]`` in place instead (``kernels/ops.slab_gemm``)
        and needs only :meth:`slot_vector`.  Callers must generation-check
        their SlotRefs first (conventions pass: slotref-gen)."""
        return _slab_take(self.bufs[name],
                          jnp.asarray(list(slots), jnp.int32))

    def slot_vector(self, experts: Sequence[int]) -> np.ndarray:  # hot-path
        """int32 slot index per expert — the scalar-prefetch operand of the
        slot-indexed GEMM (no device traffic, no weight copy)."""
        # host-sync-ok: Python-int dict reads -> host index vector
        return np.asarray([self.slot_of[e] for e in experts], np.int32)

    def summary(self) -> Dict[str, object]:
        return {"layer": self.layer, "capacity": self.capacity,
                "resident": len(self.slot_of), "writes": self.writes,
                "splice_writes": self.splice_writes,
                "d2h_bytes": self.d2h_bytes, "nbytes": self.nbytes()}


# ----------------------------------------------------------------------------
# peer-HBM slabs: expert slabs sharded over a device mesh (the P tier)
# ----------------------------------------------------------------------------
@dataclass(frozen=True)
class PeerRef:
    """Handle to one tensor of one expert inside a peer-sharded slab row.

    P-pool cache payloads carry these instead of ndarrays — the bytes live
    in the OWNER device's HBM, not in host memory and not on the compute
    device.  Validity follows the owner slot's generation, exactly like
    :class:`SlotRef`."""
    mesh_slab: "PeerSlabMesh"
    dev: int
    slot: int
    gen: int
    name: str

    @property
    def valid(self) -> bool:
        return self.mesh_slab.gen[self.dev][self.slot] == self.gen


class PeerSlabMesh:
    """Per-layer expert slabs sharded across a device mesh ('ep' axis).

    One buffer of shape ``[n_dev, capacity, *tensor_shape]`` per expert
    tensor name, laid out with ``NamedSharding(mesh, P('ep'))`` — row d
    physically lives in device d's memory.  Experts are assigned to rows by
    the EP owner rule (``distributed.sharding.ep_owner``: contiguous
    expert-id blocks), so a row is exactly the device's shard of the
    compressed store.

    * **put** — admission uploads the expert's reconstructed tensors into
      its owner row via a donated ``.at[dev, slot].set``; the upload bytes
      are charged to the ledger's ``peer_put_bytes`` (NOT the engine's h2d
      counter, which meters compute-device staging only).
    * **fetch** — a demand hit on a peer-resident expert moves its slot to
      the compute device (device 0) with one ``lax.ppermute`` per tensor
      inside a single ``shard_map`` body.  The executable is compiled once
      per source device; its per-call collective bytes are parsed from the
      optimized HLO once (``distributed.collectives.collective_bytes``) and
      charged to the ledger on every launch.  Measured fetch wall time
      feeds the :class:`~repro.core.profiles.LinkProfiler`.
    * **free/retire** — slot generations exactly as in
      :class:`DeviceSlabCache`; stale :class:`PeerRef`\\ s never serve.

    Thread model: all mutation AND fetching happens on the engine caller's
    (decode) thread — peer fetches run synchronously at submit time, so
    the single-mutator discipline of the cache pools extends unchanged.
    """

    def __init__(self, layer: int, shapes: Dict[str, Tuple[int, ...]],
                 capacity: int, mesh, *, ledger=None, link=None,
                 dtype=jnp.bfloat16):
        from jax.sharding import NamedSharding, PartitionSpec
        assert capacity > 0, capacity
        assert "ep" in mesh.axis_names, mesh.axis_names
        self.layer = layer
        self.mesh = mesh
        self.n_dev = int(mesh.shape["ep"])
        self.capacity = int(capacity)          # physical slots per device row
        self.shapes = {name: tuple(s) for name, s in shapes.items()}
        self.names = sorted(self.shapes)
        self.dtype = dtype
        self.ledger = ledger
        self.link = link
        sh = NamedSharding(mesh, PartitionSpec("ep"))
        self.bufs: Dict[str, jnp.ndarray] = {
            name: jax.device_put(
                jnp.zeros((self.n_dev, self.capacity) + tuple(s), dtype), sh)
            for name, s in self.shapes.items()}
        self.slot_of: Dict[int, Tuple[int, int]] = {}   # expert -> (dev, slot)
        self._free: List[List[int]] = [
            list(range(self.capacity - 1, -1, -1)) for _ in range(self.n_dev)]
        # per-device logical capacity (the per-device §3.4 solve may grant a
        # device fewer slots than the uniform physical row)
        self.dev_caps: List[int] = [self.capacity] * self.n_dev
        self.gen: List[List[int]] = [[0] * self.capacity
                                     for _ in range(self.n_dev)]
        self.writes = 0
        self.fetches = 0
        self.faults = None          # opt-in FaultPlan shim (core/faults)
        self.link_failures = 0      # fetch() aborts via PeerLinkError
        self._fetch_fns: Dict[int, object] = {}         # src dev -> jitted fn
        self._fetch_cost: Dict[int, Dict[str, int]] = {}  # src -> HLO bytes
        # no locks by design: all mutation on the engine caller's (decode)
        # thread; ZIPMOE_CHECK=1 asserts that (see checkz.MutatorGuard)
        self._guard = checkz.make_guard(f"PeerSlabMesh(layer={layer})")

    # -- queries -----------------------------------------------------------
    def __contains__(self, expert: int) -> bool:
        return expert in self.slot_of

    def refs(self, expert: int) -> Dict[str, PeerRef]:
        dev, slot = self.slot_of[expert]
        g = self.gen[dev][slot]
        return {name: PeerRef(self, dev, slot, g, name) for name in self.names}

    def has_free(self, dev: int) -> bool:
        used = self.capacity - len(self._free[dev])
        return bool(self._free[dev]) and used < self.dev_caps[dev]

    def expert_nbytes(self) -> int:
        """Bytes of one expert's tensors (the per-fetch payload size)."""
        n = 0
        for s in self.shapes.values():
            c = 1
            for d in s:
                c *= int(d)
            n += c * jnp.dtype(self.dtype).itemsize
        return n

    def nbytes(self) -> int:
        return sum(int(b.size) * b.dtype.itemsize for b in self.bufs.values())

    def set_dev_caps(self, caps: Sequence[int]):
        """Apply per-device logical slot counts (the per-device planner
        solves).  Shrinking below a device's occupancy only gates NEW
        admissions — residents are freed by the cache's own demotions."""
        assert len(caps) == self.n_dev, (len(caps), self.n_dev)
        self.dev_caps = [min(self.capacity, max(0, int(c))) for c in caps]

    # -- mutation (decode thread only) -------------------------------------
    def put(self, expert: int, dev: int,
            tensors: Dict[str, np.ndarray]) -> Dict[str, PeerRef]:
        """Upload `tensors` into the expert's slot in device `dev`'s row."""
        assert set(tensors) == set(self.shapes), (set(tensors),
                                                  set(self.shapes))
        self._guard.check()
        loc = self.slot_of.get(expert)
        if loc is None:
            assert self.has_free(dev), f"peer row {dev} full"
            slot = self._free[dev].pop()
            self.slot_of[expert] = loc = (dev, slot)
        else:
            assert loc[0] == dev, (expert, loc, dev)
        d, slot = loc
        didx, sidx = np.int32(d), np.int32(slot)
        nbytes = 0
        for name, val in tensors.items():
            assert tuple(val.shape) == self.shapes[name], (name, val.shape)
            # values may arrive committed to device 0 (device-staged
            # recovery, earlier peer fetches); an uncommitted host array
            # composes with the mesh-sharded buffer under any placement
            v = jnp.asarray(np.asarray(val), self.dtype)
            self.bufs[name] = _peer_set(self.bufs[name], didx, sidx, v)
            nbytes += int(v.size) * jnp.dtype(self.dtype).itemsize
        self.writes += 1
        if self.ledger is not None:
            self.ledger.charge_put(nbytes)
        return self.refs(expert)

    def free(self, expert: int):
        self._guard.check()
        loc = self.slot_of.pop(expert, None)
        if loc is None:
            return
        dev, slot = loc
        self.gen[dev][slot] += 1
        self._free[dev].append(slot)

    def retire(self):
        """Decommission the mesh slab (re-planning resized the P tier):
        every generation bumps — all outstanding PeerRefs turn stale — and
        the sharded buffers are dropped for reclamation."""
        self._guard.check()
        for dev in range(self.n_dev):
            for slot in range(self.capacity):
                self.gen[dev][slot] += 1
            self._free[dev] = list(range(self.capacity - 1, -1, -1))
        self.slot_of.clear()
        self.bufs = {}

    # -- the fetch path (decode thread; synchronous) -----------------------
    def _fetch_fn(self, src: int):
        f = self._fetch_fns.get(src)
        if f is not None:
            return f
        from jax.sharding import PartitionSpec as P
        names = self.names

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=tuple([P("ep")] * len(names)) + (P(),),
            out_specs=tuple([P("ep")] * len(names)))
        def body(*args):
            bufs, slot = args[:-1], args[-1]
            outs = []
            for b in bufs:
                # b is this shard's [1, capacity, *shape] row; pull the slot
                # and permute it from the owner to the compute device
                x = jax.lax.dynamic_index_in_dim(b[0], slot, 0,
                                                 keepdims=False)
                y = jax.lax.ppermute(x, "ep", [(src, 0)])
                outs.append(y[None])
            return tuple(outs)

        f = jax.jit(body)
        self._fetch_fns[src] = f
        # parse the compiled executable's collective bytes once per source:
        # the static per-call cost every launch charges to the ledger
        from repro.distributed.collectives import collective_bytes
        lowered = f.lower(*(self.bufs[n] for n in names), jnp.int32(0))
        self._fetch_cost[src] = collective_bytes(lowered.compile().as_text())
        return f

    def fetch(self, expert: int) -> Optional[Dict[str, jnp.ndarray]]:
        """Collective-fetch the expert's tensors to the compute device
        (device 0).  Returns {name: device array} or None when the expert
        is not (validly) resident.  Charges the ledger with the compiled
        executable's collective bytes and feeds the link profiler the
        measured wall time.  Raises :class:`PeerLinkError` when the (shim)
        link fails — the engine falls back to the local store path."""
        self._guard.check()
        loc = self.slot_of.get(expert)
        if loc is None or not self.bufs:
            return None
        if self.faults is not None:
            try:
                self.faults.peer(expert)
            except PeerLinkError:
                self.link_failures += 1
                if self.ledger is not None:
                    self.ledger.charge_failure()
                raise
        dev, slot = loc
        f = self._fetch_fn(dev)
        t0 = time.perf_counter()
        outs = f(*(self.bufs[n] for n in self.names), jnp.int32(slot))
        dev0 = jax.devices()[0]
        # commit each fetched row to the compute device so downstream
        # consumers (weight stacking) see an ordinary device-0 array
        got = {name: jax.device_put(out[0], dev0)
               for name, out in zip(self.names, outs)}
        for arr in got.values():
            arr.block_until_ready()
        dt = time.perf_counter() - t0
        self.fetches += 1
        if self.ledger is not None:
            self.ledger.charge(self._fetch_cost.get(dev, {}))
        if self.link is not None:
            self.link.record(self.expert_nbytes(), dt)
        return got

    def summary(self) -> Dict[str, object]:
        return {"layer": self.layer, "capacity": self.capacity,
                "n_dev": self.n_dev, "dev_caps": list(self.dev_caps),
                "resident": len(self.slot_of), "writes": self.writes,
                "fetches": self.fetches, "nbytes": self.nbytes()}


@functools.partial(jax.jit, donate_argnums=(0,))
def _peer_set(buf: jnp.ndarray, dev: jnp.ndarray, slot: jnp.ndarray,
              val: jnp.ndarray) -> jnp.ndarray:
    """Donated owner-row slot write; preserves the buffer's NamedSharding."""
    return buf.at[dev, slot].set(val)
