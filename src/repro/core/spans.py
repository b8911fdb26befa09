"""Named host spans at the serving path's layer boundaries.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: it records
nothing unless a profiler trace is running (``jax.profiler.start_trace``),
and then lands on the same clock as the device's operations in the
trace's ``.xplane.pb``, on the line of the thread that opened it.  ``meta``
carries ``layer=`` and, on fetch spans, ``job=`` (the fetch job's
sequence number), so a wait on the decode thread can be tied to the
worker spans that served it; they become the event's stats, not part of
its name.

``DECODE`` spans open on the decode thread (``ZipServer``'s step and the
``BatchServer`` loop around it) and nest as listed; ``WORKER`` spans open
on the fetch engine's I/O and decompression threads (and, for the
splice, wherever the engine's recovery hook runs).  A trace reduction
charges a device idle gap only to a decode-thread span: a worker span
says what the workers were doing, not what held the step.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

DECODE = (
    "zipmoe.batch.admit",        # BatchServer._admit
    "zipmoe.kv.gather",          # KVPagePool.gather of the step's views
    "zipmoe.step",               # ZipServer.decode_rows / decode_step
    "zipmoe.attn",               #   per layer: norm1 + attention (mixer)
    "zipmoe.dense_ffn",          #   per dense layer: its MLP
    "zipmoe.route",              #   per MoE layer: router to host ids
    "zipmoe.req_accounting",     #   per-request hit attribution
    "zipmoe.prefetch_submit",    #   fetch submissions (_issue_step)
    "zipmoe.fetch.wait",         #   blocked on a fetch (FetchHandle.wait_s)
    "zipmoe.fetch.collect",      #   assembly + cache admission (_collect)
    "zipmoe.fetch.reconcile",    #     slab sync inside collect
    "zipmoe.fetch.drain",        #   finished prediction jobs collected
    "zipmoe.ffn.stage",          #   token tables + expert weight sources
    "zipmoe.ffn.gemm",           #   the three slab GEMMs + the combine
    "zipmoe.shared_ffn",         #   shared experts
    "zipmoe.lm_head",            #   final norm + head
    "zipmoe.kv.commit",          # KVPagePool.commit of the new tokens
    "zipmoe.batch.sample",       # per-row sampling with its host syncs
)
WORKER = (
    "zipmoe.io.read",            # one store read (an E shard or an SM plane)
    "zipmoe.dec.decompress",     # one E shard decompressed
    "zipmoe.dec.upload",         # a tensor's two planes sent to the device
    "zipmoe.dec.splice",         # a tensor spliced on the device
)
SPANS = DECODE + WORKER


def span(name: str, **meta) -> TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``) with ``meta``."""
    return TraceAnnotation(name, **meta)
