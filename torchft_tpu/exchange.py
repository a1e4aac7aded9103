"""The cross-group gradient exchange: the bytes behind
``Manager.allreduce`` and ``Manager.reduce_scatter`` for host backends
(docs/design/allreduce_pipeline.md, docs/design/sharded_update.md).

``Manager`` keeps the protocol; this module owns what touches gradient
bytes: the bucket / chunk / slice schedule, a chunk's wire form (the
jitted packs, the int8 quantizer and its residuals), the staging window,
the put, and :class:`ShardedGrads`. :class:`GradExchange` holds the
state and drives ONE stage loop for both ops. Nothing here imports
:mod:`torchft_tpu.manager`.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from concurrent.futures import Executor, Future
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from torchft_tpu.communicator import (INT8_SEG_ELEMS, Communicator, Int8Wire,
                                      shard_bounds)
from torchft_tpu.utils import div_by_count, row_view as _row_view

logger: logging.Logger = logging.getLogger(__name__)

# S: the most wire bytes one unit of the exchange pipeline (stage ->
# fetch -> ring -> put) may hold of a single leaf. A leaf wider than
# this is cut into slices (docs/design/allreduce_pipeline.md, "Slices"):
# the first fetch and the last put, which nothing overlaps, shrink from
# the widest leaf to one slice. A constant, not an option: every group
# must cut alike. Its value is from a sweep on the chip (PERF.md, PR
# 30): smaller slices pay a few ms an op, and from 32 MiB up the host
# buffer a slice is fetched into is never-touched pages every time
# (glibc's largest mmap threshold), a D2H at 0.8 GB/s instead of 4.7.
_SLICE_BYTES = 24 << 20

_PACK_FNS: Dict[tuple, Any] = {}

# Process-wide fetch-path health counters, surfaced through
# GradExchange.metrics() (the jit caches they instrument are
# process-wide too):
#   pack_cache_misses — TRACES of the cached jitted pack fns. Counted by
#     a trace-time side effect inside the traced body, so it increments
#     exactly when jit compiles (first step per grad signature) and
#     never on a steady-state cache hit. A growing value after step 1 is
#     the per-step-retrace failure mode BENCH_r05's bf16 fetch collapse
#     was first suspected to be (ruled out by
#     tests/test_overlap.py::TestPackFetchPath, which pins it at zero).
#   put_cache_misses — TRACES of the split leaves' jitted put
#     (_put_slice), same contract: two a split leaf shape, then none.
#     Read by the tests only; not in metrics().
#   d2h_async_fallbacks — buckets whose copy_to_host_async did NOT run
#     (API absent or transient failure): their D2H serializes into the
#     fetch-wait stage instead of overlapping the ring.
_PACK_STATS: Dict[str, int] = {"pack_cache_misses": 0,
                               "put_cache_misses": 0,
                               "d2h_async_fallbacks": 0}
# Incremented from concurrent Manager worker threads (and jit tracing);
# a bare `+= 1` is a non-atomic read-modify-write that can undercount —
# and these exist as regression tripwires, where an undercount masks
# exactly what they guard.
_PACK_STATS_LOCK = threading.Lock()


def _pack_stat_bump(key: str) -> None:
    with _PACK_STATS_LOCK:
        _PACK_STATS[key] += 1


def _transfer_dtype(wire: Any) -> Optional[np.dtype]:
    """Canonical same-width unsigned-int carrier for a NON-native wire
    dtype (ml_dtypes bfloat16/float8: ``np.dtype(...).isbuiltin != 1``),
    or ``None`` for dtypes numpy owns. The D2H fetch moves the carrier's
    raw bits: PJRT's device->host fast path is only guaranteed for
    canonical dtypes, and custom-dtype buffers have been observed to
    fall onto a per-element conversion path 10x+ slower per byte (the
    BENCH_r05 bf16 fetch regression: 12.9s vs 2.9s for the SAME payload
    at half the bytes). Bitcasting inside the jitted pack is free on
    device and bitwise-invertible on host (``.view``)."""
    d = np.dtype(wire)
    if d.isbuiltin == 1:
        return None
    return np.dtype(f"u{d.itemsize}")


def _pack_leaves(leaves: list, wire_dtype_str: str,
                 rows: Optional[tuple] = None) -> Any:
    """Pack device leaves into ONE contiguous 1-D device array in the
    wire dtype, via a cached jitted concat — so the subsequent
    ``device_get`` pays a single transfer round trip for the whole chunk
    instead of one per leaf (the dominant host-allreduce cost on
    latency-bound links), and wire compression is fused into the same
    dispatch. Non-native wire dtypes (bf16) are bitcast to a canonical
    uint carrier in the same fused dispatch so the transfer itself never
    leaves the runtime's raw-bytes fast path (:func:`_transfer_dtype`);
    :meth:`GradExchange._wait_bucket` views the bits back, a zero-copy
    bitwise identity.

    ``rows = (lead, first row, row count)`` packs one slice of a single
    split leaf instead (:func:`_row_view`): the first row is TRACED, so
    a leaf costs one program for its full slices and one for its tail
    however many slices it has, and the cut is made on the leaf's first
    axis, so no leaf-sized copy is made on the way."""
    if rows is None:
        return _pack_fn(wire_dtype_str)(leaves)
    lead, first, count = rows
    return _pack_fn(wire_dtype_str, lead, count)(leaves[0],
                                                 np.int32(first))


def _pack_fn(wire_dtype_str: str, lead: Optional[int] = None,
             count: int = 0) -> Any:
    """The cached jitted pack behind :func:`_pack_leaves`: of a list of
    whole leaves, or (``lead`` given) of ``count`` rows of one leaf
    from a traced first row."""
    key = (wire_dtype_str, lead, count)
    fn = _PACK_FNS.get(key)
    if fn is None:
        wire = jnp.dtype(wire_dtype_str)
        carrier = _transfer_dtype(wire)

        def pack(parts):
            # Trace-time side effect: runs when jit COMPILES this
            # signature, never on steady-state dispatch — i.e. it counts
            # pack-executable cache misses.
            _pack_stat_bump("pack_cache_misses")
            buf = jnp.concatenate(
                [jnp.ravel(x).astype(wire) for x in parts])
            if carrier is not None:
                buf = jax.lax.bitcast_convert_type(buf, carrier)
            return buf

        def pack_rows(x, first):
            view = x.reshape((-1,) + x.shape[lead:])
            return pack([jax.lax.dynamic_slice_in_dim(
                view, first, count, axis=0)])

        fn = _PACK_FNS[key] = jax.jit(pack if lead is None
                                      else pack_rows)
    return fn


_DEV_QUANT_FNS: Dict[int, Any] = {}


def _device_quantize_pack(leaves: list, residual: Any,
                          seg_elems: int = INT8_SEG_ELEMS) -> Any:
    """Fused device-side int8 wire quantization (the D2H fetch-wall
    fix, ROADMAP item 2): one cached jitted dispatch concatenates the
    chunk's device leaves, upcasts to f32, folds in the device-resident
    error-feedback ``residual``, quantizes per segment, and emits

    * the serialized wire payload as ONE uint8 buffer laid out exactly
      like :meth:`Int8Wire.to_bytes` (``scales | zeros | q``, f32
      little-endian) — so ``copy_to_host_async`` moves ~1/4 of the f32
      bytes and the host side decodes with ``Int8Wire.from_bytes``
      zero-conversion;
    * the NEW residual (``v - dequant(q)``, non-finite entries zeroed),
      which stays on device for the next step.

    The arithmetic mirrors :meth:`Int8Wire.quantize` operation for
    operation in f32: min/max/sub/div/rint are exact or
    single-rounding, the power-of-two scale comes from integer
    exponent bits, and ``q*scale`` is exact — so the reconstruction's
    one rounding survives XLA's FMA contraction and the whole
    trajectory (payload AND residual) is bit-identical to the host
    path (frozen by tests/test_transport.py). Cached per ``seg_elems``;
    jit re-specializes per leaf-shape signature, counted by the
    trace-time ``pack_cache_misses`` bump like ``_pack_leaves``.

    The byte layout assumes a little-endian host (every supported
    deployment); the parity test would catch a BE port."""
    fn = _DEV_QUANT_FNS.get(seg_elems)
    if fn is None:

        def qpack(ls, res):
            # Trace-time side effect: counts pack-executable cache
            # misses exactly like _pack_leaves (compiles once per grad
            # signature, never on steady-state dispatch).
            _pack_stat_bump("pack_cache_misses")
            v = jnp.concatenate(
                [jnp.ravel(x).astype(jnp.float32) for x in ls])
            v = v + res
            n = v.shape[0]
            nseg = max(1, -(-n // seg_elems))
            pad = nseg * seg_elems - n
            # Pad with the last element (it belongs to the last
            # segment, so padded min/max are the true segment min/max
            # — Int8Wire.quantize pads identically).
            vp = (jnp.concatenate(
                [v, jnp.broadcast_to(v[n - 1], (pad,))]) if pad else v)
            m = vp.reshape(nseg, seg_elems)
            lo = jnp.min(m, axis=1)
            hi = jnp.max(m, axis=1)
            zero = (hi + lo) / np.float32(2.0)
            s0 = (hi - lo) / np.float32(254.0)
            finite = jnp.isfinite(zero) & jnp.isfinite(s0)
            ok = finite & (s0 > 0)
            zeros = jnp.where(finite, zero, 0.0)
            # Smallest power of two >= s0 by exponent bits — the
            # integer spelling of Int8Wire.pow2_scales, exactly
            # reproducible across numpy and XLA.
            bits = jax.lax.bitcast_convert_type(
                jnp.where(ok, s0, 1.0), jnp.uint32)
            e = (bits >> 23) + ((bits & 0x7FFFFF) != 0)
            e = jnp.clip(e, 1, 254).astype(jnp.uint32)
            scales = jnp.where(
                ok,
                jax.lax.bitcast_convert_type(e << 23, jnp.float32),
                0.0)
            qf = jnp.clip(
                jnp.rint((m - zeros[:, None]) / scales[:, None]),
                -127, 127)
            qm = jnp.where(scales[:, None] > 0, qf, 0.0).astype(
                jnp.int8)
            q = qm.reshape(-1)[:n]
            deq = (qm.astype(jnp.float32) * scales[:, None]
                   + zeros[:, None]).reshape(-1)[:n]
            new_res = v - deq
            new_res = jnp.where(jnp.isfinite(new_res), new_res, 0.0)
            payload = jnp.concatenate([
                jax.lax.bitcast_convert_type(
                    scales, jnp.uint8).reshape(-1),
                jax.lax.bitcast_convert_type(
                    zeros, jnp.uint8).reshape(-1),
                jax.lax.bitcast_convert_type(q, jnp.uint8),
            ])
            return payload, new_res

        fn = _DEV_QUANT_FNS[seg_elems] = jax.jit(qpack)
    return fn(leaves, residual)


def _stage_ahead_window() -> Optional[int]:
    """How many buckets beyond the one being waited on may hold live
    packed copies on device. ``None`` (default) = unbounded: the whole
    pytree's D2H overlaps the whole ring, at the cost of ~one extra
    grad-pytree of wire bytes at peak. ``TORCHFT_ALLREDUCE_STAGE_AHEAD``
    bounds it for HBM-tight jobs (0 restores the old one-bucket-at-a-
    time footprint)."""
    raw = os.environ.get("TORCHFT_ALLREDUCE_STAGE_AHEAD", "").strip()
    if not raw:
        return None
    try:
        return max(int(raw), 0)
    except ValueError:
        # Anyone setting this wants a CAP: fall back to the most
        # conservative bound, not to unlimited staging — a typo must not
        # invert the operator's intent into the OOM they were avoiding.
        logger.warning("non-integer TORCHFT_ALLREDUCE_STAGE_AHEAD=%r; "
                       "treating as 0 (no stage-ahead)", raw)
        return 0


_COPY_TO_HOST_ASYNC = True  # latched False once if the API is absent


def _start_copy_to_host(arr: Any) -> None:
    """Start the packed buffer's D2H DMA without blocking; the later
    batched ``device_get`` then just collects the landed bytes. Latches
    off — falling back to the plain batched device_get — only when the
    runtime's Array type lacks ``copy_to_host_async``; a transient
    runtime error skips this one copy (device_get stays correct) without
    permanently disabling the overlap for the whole process. Every
    skipped copy counts into ``allreduce_d2h_async_fallbacks``: a
    nonzero steady-state rate means the fetch stage lost its
    ring-overlap and a fetch-bound profile is explained."""
    global _COPY_TO_HOST_ASYNC
    if not _COPY_TO_HOST_ASYNC:
        _pack_stat_bump("d2h_async_fallbacks")
        return
    try:
        arr.copy_to_host_async()
    except (AttributeError, NotImplementedError, TypeError):
        _COPY_TO_HOST_ASYNC = False  # API absent on this runtime
        _pack_stat_bump("d2h_async_fallbacks")
    except Exception:  # noqa: BLE001 — transient; this copy just waits
        _pack_stat_bump("d2h_async_fallbacks")
        logger.debug("copy_to_host_async failed; falling back to "
                     "device_get for this buffer", exc_info=True)


class _ChunkPlan:
    """Geometry of one packed ring chunk: the entries ``(leaf flat index,
    element offset, element count)`` that concatenate into a single
    contiguous 1-D wire buffer of one (accumulator, wire) dtype pair.
    A chunk either holds whole leaves (every offset 0, every count the
    leaf's size; ``rows`` is None) or is ONE slice of a leaf wider than
    :data:`_SLICE_BYTES`: ``rows = (lead, first row, row count)`` of the
    leaf viewed as ``(-1,) + shape[lead:]`` (:func:`_row_view`), the
    same elements as ``offs[0]`` / ``sizes[0]`` say. Pure metadata,
    so every rank derives identical plans; doubles as the cache key
    source for the chunk's jitted unpack executable
    (:func:`_unpack_scale` / :func:`_put_slice`)."""

    __slots__ = ("orig", "wire", "idx", "offs", "sizes", "shapes", "total",
                 "rows")

    def __init__(self, orig: np.dtype, wire: np.dtype) -> None:
        self.orig = orig
        self.wire = wire
        self.idx: list = []
        self.offs: list = []
        self.sizes: list = []
        self.shapes: list = []
        self.total = 0
        self.rows: Optional[tuple] = None


class _AllreduceSchedule:
    """Memoized bucket/chunk schedule for one grad-pytree signature.
    ``buckets[b]`` lists bucket b's leaf indices (a split leaf's index
    repeats, once a slice), ``chunks[b]`` its :class:`_ChunkPlan` s,
    ``slices[i]`` how many slices leaf i was cut into (split leaves
    only)."""

    __slots__ = ("buckets", "chunks", "fingerprint", "slices")

    def __init__(self, buckets: list, chunks: list,
                 fingerprint: str, slices: Dict[int, int]) -> None:
        self.buckets = buckets
        self.chunks = chunks
        self.fingerprint = fingerprint
        self.slices = slices


def _wire_pair(dtype: Any, wire: Optional[np.dtype]) -> tuple:
    """(accumulator, wire) dtype pair for a leaf, from METADATA only.
    Wire compression applies to float leaves wider than the wire dtype;
    everything else keeps its dtype end-to-end."""
    orig = np.dtype(dtype)
    if (wire is not None and np.issubdtype(orig, np.floating)
            and orig.itemsize > wire.itemsize):
        return orig, np.dtype(wire)
    return orig, orig


def _make_buckets(shapes: list, itemsizes: list, bucket_bytes: int,
                  slice_bytes: int) -> list:
    """Greedy split of the flattened tree into buckets of entries
    ``(leaf index, element offset, element count, rows)``, preserving
    leaf order so every rank produces an identical schedule. Leaves of
    at most ``slice_bytes`` (wire bytes) group whole (``rows`` None)
    until a bucket holds >= ``bucket_bytes``; a wider leaf closes the
    open bucket and becomes consecutive single-entry buckets, one a
    slice of at most ``slice_bytes``: ``rows = (lead, first row, row
    count)`` of :func:`_row_view`, the last one shorter."""
    buckets: list = []
    cur: list = []
    cur_bytes = 0
    for i, (shape, itemsize) in enumerate(zip(shapes, itemsizes)):
        # TRUE element counts: a 0-size leaf stays at 0 (an `or 1` here
        # would make participants' packed buffers one element longer
        # than their sizes sum and wedge the ring); `or 1` is advisory
        # bucket sizing only (a scalar still costs a dispatch).
        n = int(np.prod(shape, dtype=np.int64))
        if n * itemsize > slice_bytes:
            if cur:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            lead, per = _row_view(tuple(shape), itemsize, slice_bytes)
            row = int(np.prod(shape[lead:], dtype=np.int64))
            for first in range(0, n // row, per):
                count = min(per, n // row - first)
                buckets.append([(i, first * row, count * row,
                                 (lead, first, count))])
            continue
        cur.append((i, 0, n, None))
        cur_bytes += (n or 1) * itemsize
        if cur_bytes >= bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _derive_schedule(metas: tuple, bucket_bytes: int,
                     wire_dtype: Optional[Any]) -> _AllreduceSchedule:
    """Derive the bucket + chunk schedule from per-leaf (shape, dtype)
    METADATA only: participant, healer, and spare ranks must produce
    byte-identical geometry or the ring wedges on mismatched payload
    boundaries. Buckets are sized in WIRE bytes (compressed sizes) so
    each bucket moves ~bucket_bytes over the D2H leg it amortizes;
    within a bucket, leaves group into one chunk per (accumulator, wire)
    dtype pair in first-occurrence order. A leaf wider than
    :data:`_SLICE_BYTES` on the wire is cut into consecutive slices, each
    a bucket of one chunk (:func:`_make_buckets`). ``fingerprint`` is a
    stable string of the resulting geometry, offsets included (the
    cross-rank determinism test compares it directly)."""
    wire = np.dtype(wire_dtype) if wire_dtype is not None else None
    pairs = [_wire_pair(dt, wire) for _, dt in metas]
    entries = _make_buckets([shape for shape, _ in metas],
                            [p[1].itemsize for p in pairs],
                            bucket_bytes, _SLICE_BYTES)
    chunks: list = []
    slices: Dict[int, int] = {}
    for bucket in entries:
        by_key: Dict[tuple, _ChunkPlan] = {}
        cs: list = []
        for i, off, count, rows in bucket:
            orig, wdt = pairs[i]
            key = (str(orig), str(wdt))
            c = by_key.get(key)
            if c is None:
                c = by_key[key] = _ChunkPlan(orig, wdt)
                cs.append(c)
            c.idx.append(i)
            c.offs.append(off)
            c.sizes.append(count)
            c.shapes.append(tuple(metas[i][0]))
            if rows is not None:  # a slice is alone in its bucket
                c.rows = rows
                slices[i] = slices.get(i, 0) + 1
        for c in cs:
            c.total = int(sum(c.sizes))
        chunks.append(cs)
    fingerprint = "wire-v3|" + "|".join(
        ";".join(
            f"{c.orig}:{c.wire}:" + ",".join(
                f"{i}@{off}+{n}"
                for i, off, n in zip(c.idx, c.offs, c.sizes))
            for c in cs)
        for cs in chunks)
    buckets = [[e[0] for e in bucket] for bucket in entries]
    return _AllreduceSchedule(buckets, chunks, fingerprint, slices)


_UNPACK_FNS: Dict[tuple, Any] = {}


def _unpack_scale(chunk: _ChunkPlan) -> Any:
    """Cached jitted scale-and-unpack for one chunk geometry: H2D the
    reduced 1-D buffer once, then dtype-aware 1/n + split + reshape in
    one fused device computation — the put stage's replacement for the
    host-side ``div_by_count(np.asarray(...))`` + np.split float path.
    ``n`` is traced, so membership changes don't retrace."""
    key = (str(chunk.orig), tuple(chunk.sizes), tuple(chunk.shapes))
    fn = _UNPACK_FNS.get(key)
    if fn is None:
        _bound_unpack_fns()
        splits = np.cumsum(chunk.sizes)[:-1].tolist()
        shapes = tuple(chunk.shapes)

        def unpack(buf, n):
            parts = jnp.split(buf, splits)
            return [div_by_count(p, n).reshape(s)
                    for p, s in zip(parts, shapes)]

        fn = _UNPACK_FNS[key] = jax.jit(unpack)
    return fn


def _put_slice(chunk: _ChunkPlan) -> Any:
    """Cached jitted put of one slice of a split leaf: H2D the reduced
    slice, 1/n, and write it into the leaf-shaped assembly buffer, which
    is DONATED — the leaf is assembled in place as its slices arrive,
    so the device never holds more than the leaf and one slice (the
    whole-leaf put held the reduced copy and the scaled output, twice
    the leaf). The first row and ``n`` are traced: two programs a leaf
    shape (full slices, tail) whatever the slice count."""
    shape = chunk.shapes[0]
    lead, _, count = chunk.rows
    key = (str(chunk.orig), shape, lead, count)
    fn = _UNPACK_FNS.get(key)
    if fn is None:
        _bound_unpack_fns()

        def put(buf, upd, first, n):
            # Trace-time tripwire like the packs': a put that compiles
            # after the first step of a gradient signature is a retrace.
            _pack_stat_bump("put_cache_misses")
            view = buf.reshape((-1,) + shape[lead:])
            upd = div_by_count(upd, n).reshape((count,) + shape[lead:])
            return jax.lax.dynamic_update_slice_in_dim(
                view, upd, first, axis=0).reshape(shape)

        fn = _UNPACK_FNS[key] = jax.jit(put, donate_argnums=0)
    return fn


def _bound_unpack_fns() -> None:
    # Same shape-churn bound as the schedule cache: a caller whose grad
    # shapes change every step must not leak one jitted executable per
    # geometry forever.
    if len(_UNPACK_FNS) >= 64:
        _UNPACK_FNS.clear()


class ShardedGrads:
    """This rank's canonical stripe of an averaged gradient pytree, plus
    the geometry the sharded optimizer needs (docs/design/
    sharded_update.md): ``chunks`` are the schedule's :class:`_ChunkPlan`
    objects in deterministic order, ``shards[k]`` the 1/n-scaled 1-D
    host array of chunk k's stripe ``[bounds[rank], bounds[rank+1])``
    (:func:`~torchft_tpu.communicator.shard_bounds` over the ring
    world). ``leaves`` are the ORIGINAL grad leaves — placement
    templates for reassembled params (sharding/device), never read for
    values. Produced by :meth:`Manager.reduce_scatter`, consumed by
    :meth:`FTOptimizer.apply <torchft_tpu.optim.FTOptimizer.apply>`."""

    __slots__ = ("chunks", "shards", "rank", "world", "leaves", "treedef")

    def __init__(self, chunks: list, shards: list, rank: int, world: int,
                 leaves: list, treedef: Any) -> None:
        self.chunks = chunks
        self.shards = shards
        self.rank = rank
        self.world = world
        self.leaves = leaves
        self.treedef = treedef

    def geometry_key(self) -> tuple:
        """Stripe-geometry fingerprint: the sharded optimizer's state is
        valid only while this is unchanged (a membership change moves
        every rank's stripe, so every rank resets together — params stay
        lockstep, only momentum restarts)."""
        return (self.world, self.rank,
                tuple(int(np.size(s)) for s in self.shards),
                tuple(str(c.orig) for c in self.chunks))

    def param_shards(self, params: Any) -> list:
        """Extract this rank's stripe of ``params``, chunk-aligned with
        :attr:`shards` (same flat order + bounds), as 1-D host arrays."""
        pleaves = jax.tree_util.tree_leaves(params)
        if len(pleaves) != len(self.leaves):
            raise ValueError(
                f"params have {len(pleaves)} leaves, grads had "
                f"{len(self.leaves)} — sharded update needs matching "
                "structures")
        out = []
        for c in self.chunks:
            bd = shard_bounds(c.total, self.world)
            lo, hi = int(bd[self.rank]), int(bd[self.rank + 1])
            pieces = []
            off = 0
            for i, start, size in zip(c.idx, c.offs, c.sizes):
                a, b = max(lo, off), min(hi, off + size)
                if a < b:
                    # The entry is elements [start, start + size) of
                    # its leaf (a whole leaf, or one slice of a split
                    # one), sitting at [off, off + size) of the chunk.
                    a, b = a - off + start, b - off + start
                    leaf = pleaves[i]
                    if isinstance(leaf, jax.Array):
                        # Slice on device: only this rank's 1/world of
                        # the leaf's bytes crosses D2H, not the whole
                        # leaf — the sharded update's memory/transfer
                        # win must hold on the params side too.
                        pieces.append(np.asarray(jnp.ravel(leaf)[a:b]))
                    else:
                        pieces.append(np.ravel(np.asarray(leaf))[a:b])
                off += size
            out.append(
                np.concatenate(pieces).astype(c.orig, copy=False)
                if pieces else np.empty(0, c.orig))
        return out

    def assemble_params(self, gathered: list, params: Any) -> Any:
        """Reassemble full params from every rank's updated stripes
        (``gathered[r][k]`` = rank r's stripe of chunk k, from
        :meth:`Manager.allgather_shards`), placing device leaves back on
        their original shardings. Every rank runs this on identical
        gathered bytes, so params stay bitwise lockstep."""
        pleaves, treedef = jax.tree_util.tree_flatten(params)
        out_leaves = list(pleaves)
        put_idx: list = []
        put_vals: list = []

        def place(i: int, val: np.ndarray) -> None:
            if isinstance(pleaves[i], jax.Array):
                put_idx.append(i)
                put_vals.append(val)
            else:
                out_leaves[i] = val

        split: Dict[int, np.ndarray] = {}  # leaves coming back in slices
        for k, c in enumerate(self.chunks):
            full = np.empty(c.total, c.orig)
            bd = shard_bounds(c.total, self.world)
            for r in range(self.world):
                seg = np.ravel(np.asarray(gathered[r][k])).astype(
                    c.orig, copy=False)
                want = int(bd[r + 1] - bd[r])
                if seg.size != want:
                    raise ValueError(
                        f"rank {r} published a {seg.size}-elem stripe "
                        f"for chunk {k}; geometry expects {want} — "
                        "mismatched shard_update config across groups?")
                full[bd[r]:bd[r + 1]] = seg
            if c.rows is not None:
                whole = split.get(c.idx[0])
                if whole is None:
                    whole = split[c.idx[0]] = np.empty(c.shapes[0], c.orig)
                whole.reshape(-1)[c.offs[0]:c.offs[0] + c.total] = full
                continue
            parts = np.split(full, np.cumsum(c.sizes)[:-1])
            for i, shape, part in zip(c.idx, c.shapes, parts):
                place(i, part.reshape(shape))
        for i, whole in split.items():
            place(i, whole)
        if put_idx:
            placed = jax.device_put(
                put_vals, [pleaves[i].sharding for i in put_idx])
            for i, a in zip(put_idx, placed):
                out_leaves[i] = a
        return jax.tree_util.tree_unflatten(treedef, out_leaves)


def _zero_wire_chunk(c: "_ChunkPlan", int8: bool) -> Any:
    """Healer/spare zero contribution for one ring chunk, in the wire
    format the participants are using this step: the int8 rung's affine
    zeros (exact, like zeros in any float dtype) for float chunks under
    the int8 policy, plain zeros otherwise."""
    if int8 and np.issubdtype(c.orig, np.floating):
        return Int8Wire.zeros_like(c.total)
    return np.zeros(c.total, c.wire)



def _current(residuals: Dict[tuple, Any], fingerprint: str
             ) -> Dict[tuple, Any]:
    """Bound an error-feedback residual store to the CURRENT schedule
    fingerprint: a caller whose pytree signature changes (phased
    training) must not leak one model-sized f32 residual set per
    signature, and a stale residual would fold into the WRONG elements
    (it describes different chunk geometry), so EF restarts."""
    if any(k[0] != fingerprint for k in residuals):
        return {k: v for k, v in residuals.items()
                if k[0] == fingerprint}
    return residuals


class StepFacts(NamedTuple):
    """What :class:`GradExchange` is told of the step, once a call."""

    participating: bool  # False: a healer or spare, contributing zeros
    n: int               # the put's divisor (1 in degraded mode)
    int8: bool           # the wire rung is int8 + error feedback


class GradExchange:
    """Bucketed, fetch-overlapped, wire-dtype-preserving cross-group
    exchange for host backends: :meth:`allreduce` and
    :meth:`reduce_scatter` over one stage loop (:meth:`_run`).

    The reference overlaps its cross-group allreduce with the backward
    pass per-DDP-bucket (torchft/ddp.py:47-65, manager.py:222-240). JAX
    grads materialize all at once when the jitted backward finishes, so
    the overlap available here is *between stages*: the grad pytree is
    split into buckets (sized in WIRE bytes) — small leaves grouped
    whole up to ~``bucket_bytes``, a leaf wider than ``_SLICE_BYTES``
    cut into slices of at most that, one a bucket — each bucket's
    entries packed on device into one contiguous wire-dtype buffer per
    (accumulator, wire) dtype pair, flowing through four overlapped
    stages (docs/design/allreduce_pipeline.md has the diagram). What
    nothing overlaps is the first bucket's fetch and the last one's
    put, so no bucket holds more of a leaf than a slice.

    1. caller thread, pack-dispatch (:meth:`_stage_bucket`): every
       bucket's cached jitted pack is dispatched ahead of the ring and
       its D2H DMA started at once (``copy_to_host_async``);
    2. caller thread, fetch-wait (:meth:`_wait_bucket`): per bucket, in
       order, block until its wire buffers are on host and hand them to
       the comm worker;
    3. comm worker, wire ring: ``Communicator.allreduce_wire`` keeps the
       narrow wire dtype on the TCP ring END-TO-END and folds in full
       precision (backends/host.py); uncompressed chunks take the exact
       ring, which folds into an accumulator kept across steps (handed
       back after stage 4). A reduce-scatter swaps this leg for
       ``Communicator.reduce_scatter_wire``;
    4. put thread, device scale/put (:meth:`_put_bucket_chunks`): one
       H2D transfer of the reduced buffer, a cached jitted 1/n + split +
       reshape, leaves placed like the inputs; a slice is written into
       its leaf's donated assembly buffer and the leaf handed out with
       its last slice. A reduce-scatter's stage 4 is a host 1/n of the
       local stripe, inside the ring's callback.

    Numerics guarantees (exact mode bitwise across ranks; a narrow
    wire quantizes each contribution EXACTLY ONCE, fold and 1/n in full
    precision) and the per-stage metrics are in that document too.

    Built once by its owner from what it already has: ``comm``, its span
    ``tracer`` (``fetch_dispatch``, ``fetch_wait``, ``put``), its
    counter sink ``record(**deltas)``, the single-worker ``put_executor``
    stage 4 runs on (the owner shuts both down) and
    ``set_residual_gauge(bytes)`` for ``wire_quant_residual_bytes``. Per
    call it is told three facts of the step (:class:`StepFacts`) and
    never reads the policy, the quorum, capacity or rebalance state;
    ``wire_rung`` is the opaque identity of the wire form in force
    (:meth:`set_wire`).
    """

    def __init__(self, comm: Communicator, tracer: Any,
                 record: Callable[..., None], put_executor: Executor,
                 set_residual_gauge: Callable[[float], None], *,
                 bucket_bytes: int, wire_dtype: Optional[Any],
                 wire_rung: Any, device_quant: bool) -> None:
        self._comm = comm
        self._tracer = tracer
        self._record = record
        self._put_executor = put_executor
        self._set_residual_gauge = set_residual_gauge
        self.bucket_bytes = max(int(bucket_bytes), 1)
        self.device_quant = bool(device_quant)
        self._wire_rung = wire_rung  # so set_wire flushes nothing here
        self.set_wire(wire_rung, wire_dtype)
        self._sched_cache: Dict[tuple, _AllreduceSchedule] = {}
        # Fingerprint of the schedule whose reduced buffers go back to
        # the communicator after the put (allreduce).
        self._accum_sig = ""
        # int8 + error-feedback rung: persistent per-chunk residuals,
        # keyed by (schedule fingerprint, bucket, chunk) and mutated
        # only on the caller thread that runs the stage loop; banked on
        # the host (_int8_quantize_bucket) or, by the fused device path
        # (_stage_bucket), DEVICE-resident.
        self._ef_residuals: Dict[tuple, np.ndarray] = {}
        self._dev_residuals: Dict[tuple, Any] = {}

    def set_wire(self, rung: Any, wire_dtype: Optional[Any]) -> None:
        """Install the wire form of a newly adopted policy. A change of
        rung flushes quantizer state: the int8 rung's residuals belong
        to the outgoing format and must never fold into a different
        wire's contributions — the device-resident bank included."""
        self.wire_dtype = (np.dtype(wire_dtype)
                           if wire_dtype is not None else None)
        if rung != self._wire_rung:
            self._wire_rung = rung
            self._ef_residuals.clear()
            self._dev_residuals.clear()
            self._set_residual_gauge(0.0)

    @staticmethod
    def metrics() -> Dict[str, float]:
        """Fetch-path health (:data:`_PACK_STATS`; process-wide)."""
        return {f"allreduce_{k}": float(_PACK_STATS[k])
                for k in ("pack_cache_misses", "d2h_async_fallbacks")}

    def full_shards(self, tree: Any) -> ShardedGrads:
        """World-1 :class:`ShardedGrads` of a plain averaged tree (the
        stripe is the whole flat chunk), cut by :meth:`schedule`: one
        spelling of the sharded optimizer's state and update when a
        step needed no cross-group stripe."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        chunks = [c for cs in self.schedule(treedef, leaves).chunks
                  for c in cs]
        shards = []
        for c in chunks:
            buf = np.empty(c.total, c.orig)
            off = 0
            for i, start, size in zip(c.idx, c.offs, c.sizes):
                buf[off:off + size] = np.ravel(np.asarray(leaves[i]))[
                    start:start + size].astype(c.orig, copy=False)
                off += size
            shards.append(buf)
        return ShardedGrads(chunks, shards, 0, 1, leaves, treedef)

    def schedule(self, treedef: Any, leaves: list) -> _AllreduceSchedule:
        """Memoized bucket/chunk schedule for this grad-pytree signature
        (treedef + per-leaf shape/dtype + bucket_bytes + wire_dtype):
        steady-state steps reuse the derived geometry and its cached
        pack/unpack executables instead of re-deriving per step. From
        METADATA only (:func:`_derive_schedule`), so participant, healer
        and spare ranks land on one cache entry."""
        metas = tuple(
            (tuple(np.shape(leaf)),
             str(np.dtype(getattr(leaf, "dtype", None)
                          or np.asarray(leaf).dtype)))
            for leaf in leaves)
        key = (treedef, metas, self.bucket_bytes, str(self.wire_dtype),
               _SLICE_BYTES)
        sched = self._sched_cache.get(key)
        if sched is None:
            # Tiny bound: a training loop has one or two grad signatures;
            # clearing on overflow keeps a pathological caller (changing
            # shapes every step) from leaking schedules.
            if len(self._sched_cache) >= 8:
                self._sched_cache.clear()
            sched = _derive_schedule(
                metas, self.bucket_bytes, self.wire_dtype)
            self._sched_cache[key] = sched
        return sched

    # ------------------------------------------------------- the two ops

    def allreduce(self, facts: StepFacts, tree: Any, leaves: list,
                  treedef: Any) -> Tuple[Future, Callable[[], Any]]:
        """Average ``tree`` (flattened to ``leaves`` / ``treedef``)
        across the ring. Returns the raw future of the averaged tree,
        leaves placed like the inputs, and what a failed step resolves
        to instead (the input tree); latching the error is the caller's."""
        t0 = time.perf_counter()
        sched = self.schedule(treedef, leaves)
        # Split leaves under assembly on the put thread: [buffer, slices
        # still out]. A leaf enters the result with its last slice, so
        # an aborted step (default: tree) never shows half of one.
        asm: Dict[int, list] = {i: [None, k]
                                for i, k in sched.slices.items()}

        def put(base: int, chunks: list, reduced: list) -> Dict[int, Any]:
            return self._put_bucket_chunks(chunks, reduced, leaves,
                                           facts.n, asm)

        fut = self._run(
            facts, leaves, sched, t0,
            ring_op=self._comm.allreduce_wire, put=put,
            slots=len(leaves),
            build=lambda out: jax.tree_util.tree_unflatten(treedef, out),
            counts={"allreduce_count": 1},
            executor=self._put_executor,
            # The communicator keeps the exact ring's accumulators
            # across steps when they are handed back.
            release=getattr(self._comm, "release_wire_buffers", None),
            assembling=lambda: [st[0] for st in asm.values()])
        return fut, lambda: tree

    def reduce_scatter(self, facts: StepFacts, tree: Any, leaves: list,
                       treedef: Any) -> Tuple[Future, Callable[[], Any]]:
        """:meth:`allreduce` with the ring leg swapped for
        ``Communicator.reduce_scatter_wire``: resolves to this rank's
        canonical stripe of the average as a :class:`ShardedGrads`. The
        put shrinks to a host 1/n of the local stripe, inside the ring's
        callback (there is no full-tree result to place; the updated
        params come back via the optimizer's allgather). The ring keeps
        its accumulator and resolves to a copy of the stripe, so nothing
        is handed back. A failed step resolves to zero stripes with the
        real geometry: the values are never applied (the vote aborts),
        but every rank must keep an identical step STRUCTURE."""
        t0 = time.perf_counter()
        sched = self.schedule(treedef, leaves)
        world = max(self._comm.size(), 1)
        rank = self._comm.rank()
        all_chunks = [c for cs in sched.chunks for c in cs]

        def put(base: int, chunks: list, reduced: list) -> Dict[int, Any]:
            return {base + j: div_by_count(np.asarray(s), facts.n)
                    for j, s in enumerate(reduced)}

        def zero_default() -> ShardedGrads:
            # Lazy: the zero stripes (~payload/world of fresh
            # allocation) are only materialized if the step fails.
            zs = []
            for c in all_chunks:
                bd = shard_bounds(c.total, world)
                zs.append(np.zeros(int(bd[rank + 1] - bd[rank]), c.orig))
            return ShardedGrads(all_chunks, zs, rank, world, leaves,
                                treedef)

        fut = self._run(
            facts, leaves, sched, t0,
            ring_op=self._comm.reduce_scatter_wire, put=put,
            slots=len(all_chunks),
            build=lambda out: ShardedGrads(all_chunks, out, rank, world,
                                           leaves, treedef),
            counts={"allreduce_count": 1, "reduce_scatter_count": 1})
        return fut, zero_default

    # ---------------------------------------------------- the stage loop

    def _run(self, facts: StepFacts, leaves: list,
             sched: _AllreduceSchedule, ar_t0: float, *,
             ring_op: Callable[..., Future],
             put: Callable[[int, list, list], Dict[int, Any]],
             slots: int, build: Callable[[list], Any],
             counts: Dict[str, float],
             executor: Optional[Executor] = None,
             release: Optional[Callable[[Optional[list]], None]] = None,
             assembling: Callable[[], list] = list) -> Future:
        """The one stage loop of both ops. Per bucket, in schedule order
        (the same on every rank): stage, wait, quantize, submit to
        ``ring_op``; a reduced bucket goes through ``put(its first
        chunk's flat index, chunks, reduced) -> {slot: value}`` — on
        ``executor`` when given, else inside the ring's callback — and
        the last one resolves the returned future to ``build(values by
        slot)``, recording ``counts`` and the wall since ``ar_t0``.
        ``release`` is the communicator's hand-back, for an op whose
        results are lent accumulators; ``assembling()`` then names the
        device buffers a put is still writing."""
        # Kept accumulators fit one gradient signature, so another one
        # starts afresh.
        if release is not None and sched.fingerprint != self._accum_sig:
            self._accum_sig = sched.fingerprint
            release(None)
        n_buckets = len(sched.chunks)
        agg: Future = Future()
        out: list = [None] * slots
        lock = threading.Lock()
        pending = [n_buckets]

        # Completion races: the caller thread, the comm callback, and the
        # put executor can all try to settle `agg` (first error wins). A
        # bare `if not agg.done(): agg.set_exception(...)` is check-then-act
        # across threads — the loser raises InvalidStateError *inside the
        # comm backend's callback dispatch*, surfacing as an unrelated
        # backend error. Settle through one helper that absorbs the race.
        def settle_exception(e: BaseException) -> None:
            try:
                agg.set_exception(e)
            except BaseException:  # already settled by another thread
                pass

        def finish_bucket(base: int, chunks: list, reduced: list) -> None:
            try:
                put_t0 = time.perf_counter()
                with self._tracer.span("put", chunks=len(chunks)):
                    placed = put(base, chunks, reduced)
                self._record(allreduce_put_ms_total=(
                    time.perf_counter() - put_t0) * 1e3)
                if release is not None:
                    # The put read `reduced` through an H2D transfer
                    # that may still be running (on the CPU backend it
                    # may alias the memory instead); its outputs are
                    # fresh arrays (scale + split; a split leaf's
                    # assembly buffer). Once they are ready nothing
                    # reads `reduced` any more and the next step's ring
                    # may fold into it. Before `pending` falls, so a
                    # step ends with its buffers back.
                    jax.block_until_ready((placed, assembling()))
                    if sched.fingerprint == self._accum_sig:
                        release(reduced)
                with lock:
                    for i, a in placed.items():
                        out[i] = a
                    pending[0] -= 1
                    done = pending[0] == 0
                if done:
                    self._record(
                        allreduce_ms_total=(
                            time.perf_counter() - ar_t0) * 1e3,
                        **counts)
                    # Build OUTSIDE the settle try: a custom pytree
                    # node raising there must settle agg as an error (the
                    # outer except), not be eaten by the already-settled
                    # guard and leave the caller hanging.
                    result = build(out)
                    try:
                        agg.set_result(result)
                    except BaseException:  # a bucket error settled it first
                        pass
            except Exception as e:  # noqa: BLE001
                settle_exception(e)

        def on_bucket(base: int, chunks: list, submit_t: float,
                      f: Future) -> None:
            # Ring wall = submit -> completion; includes comm-worker
            # queue wait, i.e. the serialization cost of the single
            # comm thread when buckets back up behind each other.
            self._record(allreduce_ring_ms_total=(
                time.perf_counter() - submit_t) * 1e3)
            e = f.exception()
            if e is not None:
                settle_exception(e)
            elif executor is None:
                finish_bucket(base, chunks, f.result())
            elif not agg.done():
                try:
                    executor.submit(
                        finish_bucket, base, chunks, f.result())
                except Exception as e2:  # executor shut down mid-step
                    settle_exception(e2)

        # Stage 1: dispatch pack + async D2H for buckets AHEAD of the
        # ring — by default all of them up front; a window of K
        # (_stage_ahead_window) stages at most K buckets beyond the one
        # being waited on. A bucket is a group of small leaves or one
        # slice of a wide one, so K=0 holds one packed copy of at most
        # max(bucket, slice) bytes at a time.
        window = _stage_ahead_window()
        staged: list = [None] * n_buckets
        next_to_stage = 0

        def stage_through(hi: int) -> None:
            nonlocal next_to_stage
            while next_to_stage < min(hi, n_buckets):
                staged[next_to_stage] = self._stage_bucket(
                    sched.chunks[next_to_stage], leaves,
                    bucket=next_to_stage, sched=sched, int8=facts.int8)
                next_to_stage += 1

        # Stage 2: per bucket, in order — wait for its wire buffers and
        # hand them to the comm worker (ops run in submission order
        # there, and in the same deterministic chunk order on every
        # rank) while the remaining buckets' DMA keeps flowing. Healers
        # and spares contribute zero wire buffers built from the shared
        # metadata schedule (exact in any dtype, the int8 rung's affine
        # format included). Under the int8+EF rung, float chunks not
        # quantized by the pack quantize HERE (_int8_quantize_bucket).
        base = 0
        for b, chunks in enumerate(sched.chunks):
            if facts.participating:
                stage_through(n_buckets if window is None
                              else b + 1 + window)
                bufs = self._wait_bucket(staged[b], leaves, bucket=b)
                staged[b] = None  # release the packed copies
                if facts.int8:
                    bufs = self._int8_quantize_bucket(sched, b, chunks,
                                                      bufs)
            else:
                bufs = [_zero_wire_chunk(c, facts.int8) for c in chunks]
            self._record(
                allreduce_ring_ops_total=1,
                allreduce_split_slices_total=int(
                    chunks[0].rows is not None))
            ring_op(
                bufs, [str(c.orig) for c in chunks], op="sum"
            ).add_done_callback(functools.partial(
                on_bucket, base, chunks, time.perf_counter()))
            base += len(chunks)
        return agg

    def _stage_bucket(self, chunks: list, leaves: list,
                      bucket: int = -1,
                      sched: Optional["_AllreduceSchedule"] = None,
                      int8: bool = False) -> list:
        """Fetch stage 1 (dispatch): kick off one bucket's cached jitted
        packs and start each packed buffer's D2H copy immediately —
        without blocking — so DMA overlaps the ring. Returns the
        bucket's staging records for :meth:`_wait_bucket`.

        Under the int8+EF rung with ``device_quantize`` on, all-device
        float chunks take the FUSED path (``_device_quantize_pack``):
        concat + f32 upcast + device-resident residual fold + affine
        quantize run in one jitted dispatch, and the D2H copy moves the
        serialized ``Int8Wire`` payload (~1/4 of f32) instead of the
        full-precision buffer — the dominant-stage cut of ROADMAP item
        2. The banked residual never leaves the device. With
        ``device_quantize`` off, narrow-wire chunks fetch in their
        ACCUMULATOR dtype and cast host-side (the pre-optimization
        behavior the ``multigroup_8mb_devquant_ab`` bench leg
        measures)."""
        t0 = time.perf_counter()
        with self._tracer.span("fetch_dispatch", bucket=bucket):
            recs = []
            dev_quant = False
            for j, c in enumerate(chunks):
                dev = [(jj, leaves[i]) for jj, i in enumerate(c.idx)
                       if isinstance(leaves[i], jax.Array)]
                packed = None
                kind = "pack"
                if (int8 and self.device_quant and sched is not None
                        and dev and len(dev) == len(c.idx) and c.total
                        and np.issubdtype(c.orig, np.floating)):
                    kind = "int8dev"
                    dev_quant = True
                    key = (sched.fingerprint, bucket, j)
                    self._dev_residuals = _current(
                        self._dev_residuals, sched.fingerprint)
                    res = self._dev_residuals.get(key)
                    if res is None or int(np.shape(res)[0]) != c.total:
                        res = jnp.zeros(c.total, jnp.float32)
                    packed, new_res = _device_quantize_pack(
                        [x for _, x in dev] if c.rows is None
                        # A slice is cut first, in the leaf's dtype;
                        # the quantizer then sees it as a small leaf.
                        else [_pack_leaves([dev[0][1]], str(c.orig),
                                           c.rows)],
                        res)
                    # Banked at quantize time, exactly like the host
                    # path's _ef_residuals — an aborted step keeps its
                    # residual either way.
                    self._dev_residuals[key] = new_res
                    _start_copy_to_host(packed)
                elif dev:
                    wire = c.wire
                    if not self.device_quant and wire != c.orig:
                        # A/B leg (device_quantize=False): fetch the
                        # full-precision buffer, cast host-side in
                        # _wait_bucket — the pre-fused-pack fetch cost.
                        wire = c.orig
                        kind = "hostcast"
                    packed = _pack_leaves([x for _, x in dev],
                                          str(wire), c.rows)
                    _start_copy_to_host(packed)
                recs.append((c, dev, packed, kind))
            if dev_quant:
                self._update_residual_gauge()
        ms = (time.perf_counter() - t0) * 1e3
        self._record(allreduce_fetch_dispatch_ms_total=ms,
                     allreduce_fetch_ms_total=ms)
        return recs

    def _wait_bucket(self, recs: list, leaves: list,
                     bucket: int = -1) -> list:
        """Fetch stage 2 (wait): block until this bucket's packed wire
        buffers are on host — one batched ``device_get``, which merely
        collects when the async copies already landed — and assemble the
        per-chunk ring buffers. Host-native leaves fold in here, cast to
        the wire dtype: the wire format is end-to-end, so every float
        contribution is quantized exactly once (the pre-v2 pipeline kept
        host leaves full-precision but upcast the whole payload before
        the ring, which is why bf16 only ever thinned the D2H leg)."""
        t0 = time.perf_counter()
        with self._tracer.span("fetch_wait", bucket=bucket) as wait_span:
            bufs, d2h = self._wait_bucket_inner(recs, leaves)
            wait_span.set(bytes=d2h)
        ms = (time.perf_counter() - t0) * 1e3
        self._record(
            allreduce_fetch_wait_ms_total=ms,
            allreduce_fetch_ms_total=ms,
            # Bytes that actually crossed D2H (host-native leaves never
            # do; rank-local accounting, no cross-rank constraint).
            # d2h_wire is the same quantity under its frozen name —
            # with device-side quantization these are WIRE bytes, the
            # ~1/4-of-f32 the fetch optimization exists for.
            allreduce_wire_bytes_total=float(d2h),
            allreduce_d2h_wire_bytes_total=float(d2h))
        return bufs

    def _wait_bucket_inner(self, recs: list, leaves: list) -> tuple:
        got = iter(jax.device_get(
            [p for _, _, p, _ in recs if p is not None]))
        bufs = []
        d2h = copied = 0
        for c, dev, packed, kind in recs:
            fetched = None
            if packed is not None:
                fetched = np.asarray(next(got))
                d2h += fetched.nbytes
                if kind == "int8dev":
                    # Device-quantized chunk: the fetched uint8 buffer
                    # IS the Int8Wire payload (scales | zeros | q, the
                    # to_bytes layout), bit-identical to what host-side
                    # Int8Wire.quantize would have produced — decode
                    # and hand it to the ring unchanged.
                    bufs.append(Int8Wire.from_bytes(fetched, c.total))
                    continue
                if kind == "hostcast":
                    # A/B leg: full-precision fetch, wire cast here on
                    # the host (the serialized pre-optimization cost).
                    fetched = fetched.astype(c.wire)
                    copied += fetched.nbytes
                elif fetched.dtype != c.wire:
                    # Non-native wire dtype crossed D2H as its canonical
                    # uint carrier (_transfer_dtype); view the bits back
                    # — zero-copy, bitwise identical.
                    fetched = fetched.view(c.wire)
                if len(dev) == len(c.idx):
                    # device_get's host buffer is READ-ONLY (jax marks
                    # it so); it goes to the ring as it is: the ring
                    # only reads it and folds into an accumulator of
                    # its own. No concat, no upcast, no copy.
                    bufs.append(np.ascontiguousarray(fetched))
                    continue
            # Mixed / host-only chunk: scatter the packed device parts
            # and the wire-cast host leaves into one fresh ring buffer.
            buf = np.empty(c.total, c.wire)
            copied += buf.nbytes
            offsets = np.cumsum([0] + c.sizes)
            dev_pos = {j for j, _ in dev}
            fpos = 0
            for j, i in enumerate(c.idx):
                seg = buf[offsets[j]:offsets[j + 1]]
                if j in dev_pos:
                    k = c.sizes[j]
                    seg[:] = fetched[fpos:fpos + k]
                    fpos += k
                else:
                    seg[:] = np.ravel(np.asarray(leaves[i]))[
                        c.offs[j]:c.offs[j] + c.sizes[j]].astype(
                            c.wire, copy=False)
            bufs.append(buf)
        if copied:
            self._record(allreduce_host_copy_bytes_total=float(copied))
        return bufs, d2h

    def _int8_quantize_bucket(self, sched: "_AllreduceSchedule", b: int,
                              chunks: list, bufs: list) -> list:
        """The int8+error-feedback rung's quantization stage
        (docs/design/adaptive_policy.md): fold the persistent residual
        into this step's contribution, quantize per segment
        (:class:`~torchft_tpu.communicator.Int8Wire`), and bank the new
        residual ``contribution - dequant(q)`` for the next step — the
        classic error-feedback loop that keeps repeated-average error
        bounded instead of drifting. Non-float chunks (int leaves) ride
        the exact ring unchanged. Residuals key on (schedule
        fingerprint, bucket, chunk), so a grad-signature change starts
        fresh; a wire-rung switch clears them (:meth:`set_wire`)."""
        self._ef_residuals = _current(self._ef_residuals,
                                      sched.fingerprint)
        out = []
        for j, (c, buf) in enumerate(zip(chunks, bufs)):
            if isinstance(buf, Int8Wire):
                # Already quantized ON DEVICE (the fused pack path,
                # _stage_bucket): the residual was folded and banked
                # device-side; nothing left to do host-side.
                out.append(buf)
                continue
            if not np.issubdtype(c.orig, np.floating):
                out.append(buf)
                continue
            key = (sched.fingerprint, b, j)
            v = np.ravel(np.asarray(buf)).astype(np.float32, copy=False)
            res = self._ef_residuals.get(key)
            if res is not None and res.size == v.size:
                v = v + res
            w = Int8Wire.quantize(v)
            res = v - w.dequantize(np.float32)
            # A non-finite contribution (loss-spike inf/NaN) quantized
            # to zero (Int8Wire.quantize); its residual would be
            # non-finite — banking it would poison every later step.
            # Zero it: the junk step is dropped from the EF ledger and
            # the rank recovers on the next clean contribution.
            if not np.isfinite(res).all():
                res[~np.isfinite(res)] = 0.0
            self._ef_residuals[key] = res
            out.append(w)
        self._update_residual_gauge()
        return out

    def _update_residual_gauge(self) -> None:
        """``wire_quant_residual_bytes`` = host-banked + device-banked
        EF residual footprint (device entries are f32 per element by
        construction)."""
        total = sum(r.nbytes for r in self._ef_residuals.values())
        total += sum(int(np.shape(r)[0]) * 4
                     for r in self._dev_residuals.values())
        self._set_residual_gauge(float(total))

    def _put_bucket_chunks(self, chunks: list, reduced: list,
                           leaves: list, n: int,
                           asm: Dict[int, list]) -> Dict[int, Any]:
        """Put stage of one bucket: 1/n-scale each reduced chunk and
        place the leaves back (device leaves via the cached jitted
        unpack + one batched ``device_put``; host leaves scale on
        host). Returns ``{flat leaf index: placed leaf}`` for the
        leaves this bucket COMPLETES: a slice of a split leaf goes into
        that leaf's assembly buffer in ``asm`` (``{leaf index: [buffer,
        slices still out]}``, this step's own), and the leaf is
        returned with its last slice — never half-assembled."""
        scaled: Dict[int, Any] = {}
        for c, arr in zip(chunks, reduced):
            if c.rows is not None:
                i = c.idx[0]
                leaf, st = leaves[i], asm[i]
                if isinstance(leaf, jax.Array):
                    # ONE H2D transfer of the reduced slice; the jitted
                    # 1/n + write lands it in the donated leaf-shaped
                    # buffer (leaf + one slice on the device, where the
                    # whole-leaf put below holds twice the leaf).
                    if st[0] is None:
                        st[0] = jnp.zeros(c.shapes[0], c.orig,
                                          device=leaf.sharding)
                    st[0] = _put_slice(c)(
                        st[0], np.ascontiguousarray(arr),
                        np.int32(c.rows[1]), n)
                else:
                    if st[0] is None:
                        st[0] = np.empty(c.shapes[0], c.orig)
                    st[0].reshape(-1)[c.offs[0]:c.offs[0] + c.total] = (
                        div_by_count(np.asarray(arr), n))
                st[1] -= 1
                if st[1] == 0:
                    out = asm.pop(i)[0]
                    scaled[i] = (jax.device_put(out, leaf.sharding)
                                 if isinstance(leaf, jax.Array) else out)
                continue
            if c.total and all(isinstance(leaves[i], jax.Array)
                               for i in c.idx):
                # All-device chunk: ONE H2D transfer of the reduced
                # buffer, then the schedule's cached jitted 1/n-scale +
                # split + reshape runs on device — the put stage stays
                # off the Python float path entirely (no host div, no
                # per-leaf np.split copies). n is traced, so membership
                # changes don't retrace.
                outs = _unpack_scale(c)(np.ascontiguousarray(arr), n)
                placed = jax.device_put(
                    list(outs), [leaves[i].sharding for i in c.idx])
                for i, a in zip(c.idx, placed):
                    scaled[i] = a
                continue
            # Host / mixed / empty chunk: host-side scale+split, device
            # leaves restored in one batched put.
            arr = div_by_count(np.asarray(arr), n)
            parts = np.split(arr, np.cumsum(c.sizes)[:-1])
            put_idx: list = []
            put_vals: list = []
            for i, shape, part in zip(c.idx, c.shapes, parts):
                val = part.reshape(shape)
                if isinstance(leaves[i], jax.Array):
                    put_idx.append(i)
                    put_vals.append(val)
                else:
                    scaled[i] = val
            if put_idx:
                placed = jax.device_put(
                    put_vals, [leaves[i].sharding for i in put_idx])
                for i, a in zip(put_idx, placed):
                    scaled[i] = a
        return scaled

