"""Pytree (de)serialization for checkpoint transfer and host collectives.

The reference streams ``torch.save``/``torch.load`` state dicts over HTTP for
healing (/root/reference/torchft/checkpointing.py:50-103). Here state is a JAX
pytree (params / optimizer state / manager metadata), serialized with a small
self-describing binary format:

    [8B magic "TFTPTREE"][u32 header_len][header json][raw array bytes...]

The header carries the flattened key paths, dtypes, and shapes; leaves are
``jax.device_get`` materialized and written raw. Restoring goes through
``jax.device_put`` with an optional target sharding, which is the TPU-native
healing move: weights arrive over DCN on the host and are laid out directly
onto the receiving slice's mesh.

Both directions stream: the header is computed from array *metadata* (no
data fetched), then :func:`iter_pytree_chunks` materializes a batch at a
time (a wide leaf in runs of rows, the next batch fetched while this one
is consumed) and yields zero-copy memoryview slices, and
:func:`load_pytree_from` fills preallocated buffers leaf-by-leaf with
per-leaf ``device_put``. Peak extra host RAM is two batches on the sending
side and O(largest leaf + chunk) on the receiving one, not O(checkpoint)
— healing a config-3-sized model (80GB+ params+opt) cannot double host RAM
the way a monolithic ``bytes`` round-trip would (the reference streams via
``torch.save`` directly to the socket for the same reason,
/root/reference/torchft/checkpointing.py:63-72).

No pickle anywhere — unlike ``torch.load``, a malicious checkpoint peer
cannot execute code on the healer.
"""

from __future__ import annotations

import functools
import itertools
import json
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import (Any, BinaryIO, Callable, Dict, Iterable, Iterator, List,
                    Optional, Tuple)

import jax
import numpy as np

from torchft_tpu.utils import row_view

_MAGIC = b"TFTPTREE"
DEFAULT_CHUNK_BYTES = 8 * 1024 * 1024


class LeafDigestMismatch(ValueError):
    """A leaf's bytes failed crc32 verification against its manifest
    digest — corrupt or torn data that must never reach the device."""


def _dtype_name(dt: np.dtype) -> str:
    # ml_dtypes extension types (bfloat16, fp8 variants) stringify to void
    # via .str; their .name round-trips through _resolve_dtype.
    return dt.name


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))

# Non-array leaves (python ints/floats/strings/bools/None) are stored in the
# header directly; arrays are stored as raw bytes.


def _key_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        elif isinstance(p, jax.tree_util.FlattenedIndexKey):
            parts.append(str(p.key))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _is_array_leaf(leaf: Any) -> bool:
    return isinstance(leaf, (np.ndarray, np.generic, jax.Array))


class PytreePlan:
    """Streaming plan for one serialized pytree: the preamble (magic +
    header), the total serialized length, the array leaves in body order,
    and the parsed header dict. Iterates/unpacks like the historical
    ``(preamble, total_len, array_leaves)`` tuple so existing callers keep
    working.

    Per-leaf content digests (:meth:`digests`) are computed LAZILY — the
    plan itself stays metadata-only (no device data fetched) until a
    caller (the checkpoint server's manifest endpoint) actually needs
    them, and the one digesting pass is cached so N healers / resumed
    attempts against the same snapshot pay for it once."""

    __slots__ = ("preamble", "total_len", "array_leaves", "header",
                 "_digests", "_digest_lock")

    def __init__(self, preamble: bytes, total_len: int,
                 array_leaves: list, header: dict) -> None:
        self.preamble = preamble
        self.total_len = total_len
        self.array_leaves = array_leaves
        self.header = header
        self._digests: Optional[List[int]] = None
        self._digest_lock = threading.Lock()

    # --- legacy (preamble, total_len, array_leaves) tuple protocol ------
    def __iter__(self):
        return iter((self.preamble, self.total_len, self.array_leaves))

    def __getitem__(self, i):
        return (self.preamble, self.total_len, self.array_leaves)[i]

    def __len__(self) -> int:
        return 3

    def digests(self, batch_bytes: int = 0,
                clock: Optional["StageClock"] = None) -> List[int]:
        """Per-array-leaf crc32 of the raw serialized bytes, in body
        order. Computed once and cached; safe under concurrent manifest
        requests. The pass is the fetch engine's (:func:`_iter_leaf_views`):
        the next batch crosses D2H while this one is digested, a leaf
        wider than a batch is digested slice by slice into one running
        crc, and host RAM holds two batches. crc32 is not cryptographic —
        it detects truncation/corruption in transit, and doubles as the
        runtime check of the cross-donor same-step bitwise-identity
        invariant (donors for one step must produce identical digests).
        ``clock`` takes the pass's D2H busy time (``fetch``)."""
        with self._digest_lock:
            if self._digests is None:
                bb = batch_bytes or DEFAULT_BATCH_BYTES
                self._digests = list(leaf_digests(
                    _iter_leaf_views(self.array_leaves, bb, clock=clock),
                    self.array_leaves))
            return list(self._digests)


def leaf_digests(views: Iterable[Tuple[int, int, memoryview]],
                 array_leaves: list,
                 sink: Optional[Callable[[memoryview], Any]] = None
                 ) -> Iterator[int]:
    """Fold :func:`_iter_leaf_views` pieces into one crc32 a leaf, in
    body order: a leaf's digest is yielded with its last piece. ``sink``
    sees every piece first (a writer that digests what it writes)."""
    crc = 0
    for i, off, mv in views:
        if sink is not None:
            sink(mv)
        crc = zlib.crc32(mv, crc)
        if off + len(mv) == _leaf_nbytes(array_leaves[i]):
            yield crc
            crc = 0


def manifest_from(plan: PytreePlan,
                  digests: Optional[List[int]] = None) -> dict:
    """Digest manifest of one serialized pytree: the header's leaf
    entries with each array entry annotated with its ``crc32`` content
    digest, plus the stream geometry (``preamble_len``/``total_len``) a
    range-resuming or verifying reader needs. The shared spelling under
    the heal transport's ``/manifest`` endpoint and the durable
    checkpoint trailer (:mod:`torchft_tpu.checkpoint_io`). ``digests``
    reuses crcs already computed (e.g. fused into a write pass);
    otherwise :meth:`PytreePlan.digests` fetches and digests the
    leaves."""
    digs = iter(digests if digests is not None else plan.digests())
    leaves = []
    for e in plan.header["leaves"]:
        e = dict(e)
        if e["kind"] == "array":
            e["crc32"] = next(digs)
        leaves.append(e)
    return {
        "digest": "crc32",
        "preamble_len": len(plan.preamble),
        "total_len": int(plan.total_len),
        "leaves": leaves,
    }


# ------------------------------------------------- state attestation
# docs/design/state_attestation.md: the cross-group committed-params
# fingerprint. Per leaf, over the RAW little-endian bytes:
#   w0 = sum(byte_i)            mod 2^32   (catches every single-byte
#                                           corruption outright)
#   w1 = sum((i+1) * byte_i)    mod 2^32   (position-weighted: catches
#                                           transposed / relocated bytes)
# folded across leaves in pytree order with FNV-style u32 multiply-add
# into FOUR accumulator words (the two sums, the byte-length chain, and
# a rotate-xor mix). ALL arithmetic is u32 wraparound — exact on every
# backend, so the jitted device fold in manager.py and this NumPy
# reference are bit-identical (frozen by tests/test_attestation.py).
# crc32 (the heal/publish manifests above) is NOT reused here: it is
# inherently sequential per leaf, while these sums are one fused
# data-parallel reduction a jitted kernel can run on device without an
# extra D2H of the params.

ATTEST_FNV_PRIME = 0x01000193
ATTEST_FNV_BASIS = 0x811C9DC5
_M32 = 0xFFFFFFFF


def attest_leaf_words(arr: Any) -> Tuple[int, int, int]:
    """``(w0, w1, nbytes mod 2^32)`` of one leaf's raw bytes — the
    NumPy reference spelling of the device kernel's per-leaf stage."""
    a = np.asarray(arr)
    b = np.frombuffer(a.tobytes(), dtype=np.uint8).astype(np.uint64)
    n = b.size
    w0 = int(b.sum()) & _M32
    pos = (np.arange(n, dtype=np.uint64) + 1) & _M32
    # u64 products are exact (< 2^40); a u64 sum that wraps still
    # agrees mod 2^32 with the device's per-add u32 wraparound.
    w1 = int((pos * b).sum()) & _M32
    return w0, w1, n & _M32


def attest_fold(acc: List[int], w0: int, w1: int, n32: int) -> List[int]:
    """Fold one leaf's words into the 4-word accumulator (u32
    wraparound multiply-add; the device kernel runs the same ops in
    ``uint32``)."""
    p = ATTEST_FNV_PRIME
    rot = ((w1 << 1) | (w1 >> 31)) & _M32
    return [
        (acc[0] * p + w0) & _M32,
        (acc[1] * p + w1) & _M32,
        (acc[2] * p + n32) & _M32,
        ((acc[3] ^ w0 ^ rot) * p) & _M32,
    ]


def attest_combine(words: Any) -> str:
    """Render the 4 accumulator words as the 32-hex-char state digest
    string every StepDigest carries — one spelling for the device path
    (manager.py hands the fetched u32 words here) and the reference."""
    return "".join(f"{int(w) & _M32:08x}" for w in words)


def attest_fingerprint(leaves: List[Any]) -> str:
    """NumPy reference of the full committed-state fingerprint: fold
    every array leaf (pytree order) and combine. The oracle the jitted
    device digest is frozen against, and the host fallback when a
    state tree holds no device arrays at all."""
    acc = [ATTEST_FNV_BASIS] * 4
    for leaf in leaves:
        acc = attest_fold(acc, *attest_leaf_words(leaf))
    return attest_combine(acc)


def manifest_delta(old: Optional[dict], new: dict) -> dict:
    """Changed-leaf summary between two digest manifests of the same
    pytree structure — the delta-publication primitive
    (docs/design/serving.md): an array leaf is *changed* when its key
    has no counterpart in ``old`` or its crc32 differs, and a
    subscriber holding the ``old`` generation needs to fetch exactly
    the changed leaves to reach ``new``. Returns ``{"changed":
    [body-order array indices], "changed_bytes", "total_bytes",
    "leaves"}``. ``old=None`` (cold subscriber) marks every array leaf
    changed."""
    old_crcs: Dict[str, int] = {}
    if old is not None:
        for e in old.get("leaves", ()):
            if e.get("kind") == "array" and "crc32" in e:
                old_crcs[e["key"]] = int(e["crc32"])
    changed: List[int] = []
    changed_bytes = 0
    total_bytes = 0
    arr_idx = 0
    for e in new["leaves"]:
        if e.get("kind") != "array":
            continue
        nbytes = int(e["nbytes"])
        total_bytes += nbytes
        want = e.get("crc32")
        if want is None or old_crcs.get(e["key"]) != int(want):
            changed.append(arr_idx)
            changed_bytes += nbytes
        arr_idx += 1
    return {"changed": changed, "changed_bytes": changed_bytes,
            "total_bytes": total_bytes, "leaves": arr_idx}


def plan_pytree(tree: Any) -> PytreePlan:
    """Compute the serialized header from leaf *metadata* only — no device
    data is fetched. Returns a :class:`PytreePlan` (unpacks as the legacy
    ``(preamble_bytes, total_len, array_leaves)`` tuple) where
    ``preamble_bytes`` is magic+header, ``total_len`` the full serialized
    size (so HTTP can send Content-Length before streaming), and
    ``array_leaves`` the leaves whose raw bytes follow, in body order."""
    leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
    header: dict = {"leaves": []}
    array_leaves: list = []
    offset = 0
    for path, leaf in leaves_with_path:
        key = _key_str(path)
        if _is_array_leaf(leaf):
            dt = np.dtype(leaf.dtype)
            shape = list(leaf.shape)
            nbytes = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
            header["leaves"].append({
                "key": key,
                "kind": "array",
                "dtype": _dtype_name(dt),
                "shape": shape,
                "offset": offset,
                "nbytes": nbytes,
            })
            array_leaves.append(leaf)
            offset += nbytes
        else:
            header["leaves"].append({"key": key, "kind": "py", "value": leaf})
    hdr = json.dumps(header).encode()
    preamble = _MAGIC + len(hdr).to_bytes(4, "little") + hdr
    return PytreePlan(preamble, len(preamble) + offset, array_leaves, header)


# What one ``device_get`` of the fetch engine lands on the host, and the
# widest run of rows a leaf is cut into. Under glibc's largest mmap
# threshold (32 MiB): such a buffer comes from the heap and is recycled
# warm, where from 32 MiB up each is a new mapping of never-touched pages
# that other threads' faults then wait behind (``exchange._SLICE_BYTES``
# is the same size for the same reason; PERF.md, Findings PRs 30 and 36).
DEFAULT_BATCH_BYTES = 24 * 1024 * 1024


class StageClock:
    """Busy seconds by stage, added to from the several threads of a
    pipelined transfer. The heal keeps one a side: the donor's
    ``fetch`` / ``send``, the healer's ``manifest`` / ``recv`` /
    ``verify`` / ``place``. Their sum over the transfer's wall says how
    far the stages ran beside each other (1.0: one after the other)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds: Dict[str, float] = {}

    def add(self, stage: str, seconds: float) -> None:
        with self._lock:
            self._seconds[stage] = self._seconds.get(stage, 0.0) + seconds

    def ms(self, stage: str) -> float:
        with self._lock:
            return self._seconds.get(stage, 0.0) * 1e3


def balanced_ranges(sizes: list, n: int) -> list:
    """Contiguous byte-balanced ``[start, stop)`` index ranges, one per
    group (possibly empty), partitioning ``range(len(sizes))``. The one
    stripe partitioner shared by the sharded checkpoint writer
    (``checkpoint_io.save_sharded``) and the striped-heal fetch planner
    (``checkpointing._HealSession.stripes``) — their geometries must not
    drift apart."""
    total = float(sum(sizes)) or 1.0
    ranges = []
    start = 0
    acc = 0.0
    g = 0
    for i, sz in enumerate(sizes):
        acc += sz
        while g < n - 1 and acc >= total * (g + 1) / n:
            ranges.append((start, i + 1))
            start = i + 1
            g += 1
    while len(ranges) < n:
        ranges.append((start, len(sizes)))
        start = len(sizes)
    return ranges


def _leaf_nbytes(leaf: Any) -> int:
    return int(np.prod(leaf.shape, dtype=np.int64)
               ) * np.dtype(leaf.dtype).itemsize


@functools.lru_cache(maxsize=64)
def _cut_rows(lead: int, count: int) -> Any:
    """Jitted cut of ``count`` rows of :func:`row_view`'s view from a
    TRACED first row: a leaf shape costs one program for its full slices
    and one for its tail, however many slices it has."""
    def cut(x, first):
        view = x.reshape((-1,) + x.shape[lead:])
        return jax.lax.dynamic_slice_in_dim(view, first, count, axis=0)
    return jax.jit(cut)


def _fetch_units(array_leaves: list, batch_bytes: int,
                 span: Optional[Tuple[int, int]] = None) -> List[list]:
    """The fetch engine's plan, from metadata alone: units of pieces
    ``(leaf index, byte offset in the leaf, nbytes, rows)``, one
    ``device_get`` a unit, in body order. Leaves of at most
    ``batch_bytes`` group whole (``rows`` None) up to that many bytes a
    unit; a wider leaf becomes consecutive units of one piece each,
    ``rows = (lead, first row, row count)`` of :func:`row_view`. ``span``
    keeps only the pieces that overlap body bytes ``[lo, hi)``."""
    def wanted(at: int, n: int) -> bool:
        return span is None or max(span[0], at) < min(span[1], at + n)

    units: List[list] = []
    cur: list = []
    cur_bytes = 0
    start = 0
    for i, leaf in enumerate(array_leaves):
        nbytes = _leaf_nbytes(leaf)
        base, start = start, start + nbytes
        if nbytes > batch_bytes:
            if cur:
                units.append(cur)
                cur, cur_bytes = [], 0
            shape = tuple(leaf.shape)
            lead, per = row_view(shape, np.dtype(leaf.dtype).itemsize,
                                 batch_bytes)
            row_bytes = nbytes // int(np.prod(shape[:lead], dtype=np.int64))
            for first in range(0, nbytes // row_bytes, per):
                count = min(per, nbytes // row_bytes - first)
                if wanted(base + first * row_bytes, count * row_bytes):
                    units.append([(i, first * row_bytes, count * row_bytes,
                                   (lead, first, count))])
            continue
        if not wanted(base, nbytes):
            continue
        if cur and cur_bytes + nbytes > batch_bytes:
            units.append(cur)
            cur, cur_bytes = [], 0
        cur.append((i, 0, nbytes, None))
        cur_bytes += nbytes
    if cur:
        units.append(cur)
    return units


def _fetch_unit(array_leaves: list, unit: list,
                clock: Optional[StageClock]) -> list:
    """One batched ``device_get``: the unit's pieces as ``(leaf index,
    byte offset in the leaf, uint8 memoryview)``."""
    t0 = time.perf_counter()
    parts = []
    for i, _, _, rows in unit:
        leaf = array_leaves[i]
        if rows is not None:
            lead, first, count = rows
            if isinstance(leaf, jax.Array):
                leaf = _cut_rows(lead, count)(leaf, np.int32(first))
            else:
                leaf = np.asarray(leaf)
                leaf = leaf.reshape((-1,) + leaf.shape[lead:])[
                    first:first + count]
        parts.append(leaf)
    out = [(i, off, np.ascontiguousarray(arr).reshape(-1).view(np.uint8).data)
           for (i, off, _, _), arr in zip(unit, jax.device_get(parts))]
    if clock is not None:
        clock.add("fetch", time.perf_counter() - t0)
    return out


def _one_ahead(thunks: Iterable[Callable[[], Any]]) -> Iterator[Any]:
    """``thunk()`` for each thunk, in order, the next one computed on a
    helper thread while the consumer holds the current: two results
    alive at most. One thunk alone runs inline, with no thread."""
    thunks = iter(thunks)
    first = next(thunks, None)
    if first is None:
        return
    pending = next(thunks, None)
    if pending is None:
        yield first()
        return
    with ThreadPoolExecutor(1, thread_name_prefix="tft-fetch") as pool:
        fut = pool.submit(first)
        while fut is not None:
            cur = fut.result()
            fut = pool.submit(pending) if pending is not None else None
            pending = next(thunks, None)
            yield cur
            del cur


def _iter_leaf_views(array_leaves: list, batch_bytes: int,
                     span: Optional[Tuple[int, int]] = None,
                     clock: Optional[StageClock] = None,
                     ) -> Iterator[Tuple[int, int, memoryview]]:
    """Host-materialize ``array_leaves`` a :func:`_fetch_units` unit at a
    time and yield ``(leaf index, byte offset in the leaf, uint8
    memoryview)`` per piece, in body order — the shared fetch engine
    under streaming serialization, digest computation and the durable
    checkpoint's writer. A leaf of at most ``batch_bytes`` is one piece;
    a wider one comes in runs of whole rows, so no ``device_get`` (and no
    write after it) is longer than a batch. The next unit crosses D2H
    on a helper thread while the consumer holds the current one
    (:func:`_one_ahead`): host RAM holds two batches, 2 x
    ``batch_bytes``, whatever the tree; a tree that fits one batch is
    fetched inline. ``clock`` takes each ``device_get``'s time as
    ``fetch``."""
    for pieces in _one_ahead(
            functools.partial(_fetch_unit, array_leaves, unit, clock)
            for unit in _fetch_units(array_leaves, batch_bytes, span)):
        yield from pieces


def iter_pytree_chunks(tree: Any,
                       chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                       plan: Optional[Any] = None,
                       batch_bytes: int = DEFAULT_BATCH_BYTES,
                       start: int = 0,
                       end: Optional[int] = None,
                       clock: Optional[StageClock] = None,
                       ) -> Iterator[memoryview]:
    """Stream-serialize: yields the preamble, then the array leaves' raw
    bytes in ``chunk_bytes`` slices. Leaves are host-materialized by the
    fetch engine (:func:`_iter_leaf_views`): small ones in batched
    ``jax.device_get`` groups of up to ``batch_bytes`` (a pytree with
    thousands of small optimizer-state leaves pays a handful of dispatch
    round-trips, not thousands), wide ones in runs of rows, the next
    batch fetched while this one is consumed, so peak extra host RAM is
    two batches, not O(checkpoint). Slices are zero-copy memoryviews.
    ``plan`` reuses a precomputed :func:`plan_pytree` result (the HTTP
    server plans once for Content-Length and must stream that same plan).

    ``start``/``end`` select a byte range of the serialized stream
    (``end=None`` = to the end): what lies wholly outside the range is
    skipped WITHOUT fetching any device data, which is what makes a
    resumed heal transfer O(remaining bytes) on the donor side too, not
    just on the wire."""
    preamble, total_len, array_leaves = (
        plan if plan is not None else plan_pytree(tree))
    hi = total_len if end is None else min(int(end), total_len)
    lo = max(int(start), 0)
    full = lo == 0 and hi >= total_len
    if full:
        # Bitwise the historical full stream, chunk for chunk where no
        # leaf is cut: the preamble whole, and the single empty chunk a
        # 0-size leaf yields.
        yield memoryview(preamble)
    elif lo >= hi:
        return
    elif lo < len(preamble):
        mv = memoryview(preamble)[lo:min(hi, len(preamble))]
        for k in range(0, len(mv), chunk_bytes):
            yield mv[k:k + chunk_bytes]
    body = (max(lo - len(preamble), 0), hi - len(preamble))
    bases = [0, *itertools.accumulate(map(_leaf_nbytes, array_leaves))]
    for i, off, mv in _iter_leaf_views(array_leaves, batch_bytes,
                                       None if full else body, clock):
        if not full:
            at = bases[i] + off
            mv = mv[max(body[0] - at, 0):body[1] - at]
        for k in range(0, len(mv) or int(full), chunk_bytes):
            yield mv[k:k + chunk_bytes]


def save_pytree(tree: Any) -> bytes:
    """Serialize a pytree of arrays/scalars to one buffer. Device fetches
    are batched (see :func:`iter_pytree_chunks`), so the per-step host
    collective path (``backends/host.py``) pays one dispatch round-trip
    per ~64MB, not per leaf. For O(batch) RAM streaming to a socket/file,
    use :func:`iter_pytree_chunks` directly."""
    return b"".join(iter_pytree_chunks(tree))


def _read_exact_into(fp: BinaryIO, mv: memoryview) -> None:
    got = 0
    while got < len(mv):
        if hasattr(fp, "readinto"):
            n = fp.readinto(mv[got:])
        else:  # file-likes without readinto (e.g. raw HTTPResponse wrappers)
            chunk = fp.read(len(mv) - got)
            n = len(chunk)
            mv[got:got + n] = chunk
        if not n:
            raise ValueError("truncated checkpoint stream")
        got += n


def _read_exact(fp: BinaryIO, n: int) -> bytes:
    buf = bytearray(n)
    _read_exact_into(fp, memoryview(buf))
    return bytes(buf)


def _match_entries(header: dict, target: Any):
    """Validate checkpoint entries against the flattened target: positional
    + name cross-check, array entries must meet an array target with equal
    shape AND dtype, py entries must meet a non-array target. The header is
    untrusted (a malicious/corrupt peer), so this is what bounds allocations
    to target size and guarantees a structural mismatch fails loudly instead
    of silently permuting or substituting weights. Returns
    ``(pairs, treedef)``."""
    tpaths, treedef = jax.tree_util.tree_flatten_with_path(target)
    entries = header["leaves"]
    if len(entries) != len(tpaths):
        raise ValueError(
            f"checkpoint has {len(entries)} leaves, target has {len(tpaths)}")
    pairs = []
    for entry, (path, tleaf) in zip(entries, tpaths):
        key = _key_str(path)
        if entry["key"] != key:
            raise ValueError(
                f"checkpoint leaf {entry['key']!r} does not match target "
                f"leaf {key!r}")
        if entry["kind"] == "array":
            if not _is_array_leaf(tleaf):
                raise ValueError(
                    f"checkpoint leaf {key!r} is an array but the target "
                    f"leaf is not")
            if tuple(entry["shape"]) != tuple(tleaf.shape):
                raise ValueError(
                    f"checkpoint leaf {key!r} has shape "
                    f"{tuple(entry['shape'])}, target expects "
                    f"{tuple(tleaf.shape)}")
            if _resolve_dtype(entry["dtype"]) != np.dtype(tleaf.dtype):
                raise ValueError(
                    f"checkpoint leaf {key!r} has dtype {entry['dtype']}, "
                    f"target expects {np.dtype(tleaf.dtype).name}")
        elif _is_array_leaf(tleaf):
            raise ValueError(
                f"checkpoint leaf {key!r} is a py value but the target "
                f"leaf is an array")
        pairs.append((entry, tleaf))
    return pairs, treedef


def load_pytree_from(
    fp: BinaryIO,
    target: Any,
    device_put_fn: Optional[Callable[[np.ndarray, Any], Any]] = None,
    digests: Optional[List[int]] = None,
) -> Any:
    """Restore a pytree from a binary stream into the structure of
    ``target``, incrementally: each array leaf is read into a preallocated
    buffer and handed to ``device_put_fn`` before the next leaf is read, so
    peak extra host RAM is one leaf, not the whole checkpoint.

    ``target`` supplies the tree structure (and, when ``device_put_fn`` is
    given, per-leaf placement: it is called as ``device_put_fn(np_array,
    target_leaf)`` so healers can restore directly onto their mesh sharding).
    Keys are matched positionally against the flattened target and
    cross-checked by name, so a structural mismatch fails loudly instead of
    silently permuting weights.

    ``digests``, when given, is the per-array-leaf crc32 list (body
    order, e.g. from a :func:`manifest_from` manifest): every leaf is
    digest-verified after the read and BEFORE ``device_put_fn`` — the
    same corrupt-bytes-never-reach-the-device discipline as the heal
    path — raising :class:`LeafDigestMismatch` on the first mismatch.
    """
    try:
        magic = _read_exact(fp, len(_MAGIC))
    except ValueError:
        raise ValueError("not a torchft_tpu pytree checkpoint")
    if magic != _MAGIC:
        raise ValueError("not a torchft_tpu pytree checkpoint")
    hdr_len = int.from_bytes(_read_exact(fp, 4), "little")
    # Untrusted length: cap before allocating (headers are ~100B of JSON
    # per leaf; 256MiB covers millions of leaves, while 0xFFFFFFFF from a
    # corrupt peer would otherwise allocate 4GiB up front).
    if hdr_len > 256 * 1024 * 1024:
        raise ValueError(f"checkpoint header implausibly large ({hdr_len}B)")
    header = json.loads(_read_exact(fp, hdr_len))

    pairs, treedef = _match_entries(header, target)
    digs = iter(digests) if digests is not None else None
    out_leaves = []
    for entry, tleaf in pairs:
        if entry["kind"] == "py":
            out_leaves.append(entry["value"])
            continue
        # Shape/dtype already validated against the target by
        # _match_entries, so this allocation is exactly target-leaf-sized.
        arr = np.empty(entry["shape"], dtype=_resolve_dtype(entry["dtype"]))
        mv = arr.reshape(-1).view(np.uint8).data
        _read_exact_into(fp, mv)
        if digs is not None:
            try:
                want = int(next(digs))
            except StopIteration:
                raise LeafDigestMismatch(
                    f"digest list exhausted at leaf {entry['key']!r} — "
                    "manifest does not cover this stream") from None
            got = zlib.crc32(mv)
            if got != want:
                raise LeafDigestMismatch(
                    f"leaf {entry['key']!r} failed digest verification "
                    f"(crc32 {got:08x} != manifest {want:08x})")
        if device_put_fn is not None:
            # device_put immediately: jax owns the transfer, the host buffer
            # is released as soon as the copy lands, and the next leaf's
            # read overlaps this leaf's host->device DMA.
            out_leaves.append(device_put_fn(arr, tleaf))
        else:
            out_leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def load_pytree(
    data: Any,
    target: Any,
    device_put_fn: Optional[Callable[[np.ndarray, Any], Any]] = None,
) -> Any:
    """Restore from an in-memory buffer (bytes/bytearray/memoryview),
    zero-copy: without ``device_put_fn``, returned arrays are
    ``np.frombuffer`` views onto ``data`` — this is the per-step host
    collective path (``backends/host.py`` hands in the received bytearray).
    For incremental restore from a socket/file use :func:`load_pytree_from`.
    """
    if len(data) < len(_MAGIC) or bytes(data[:len(_MAGIC)]) != _MAGIC:
        raise ValueError("not a torchft_tpu pytree checkpoint")
    hdr_len = int.from_bytes(data[len(_MAGIC):len(_MAGIC) + 4], "little")
    body_start = len(_MAGIC) + 4 + hdr_len
    if len(data) < body_start:
        raise ValueError("truncated checkpoint stream")
    header = json.loads(bytes(data[len(_MAGIC) + 4:body_start]))

    pairs, treedef = _match_entries(header, target)
    out_leaves = []
    for entry, tleaf in pairs:
        if entry["kind"] == "py":
            out_leaves.append(entry["value"])
            continue
        count = int(np.prod(entry["shape"], dtype=np.int64))
        if body_start + entry["offset"] + entry["nbytes"] > len(data):
            raise ValueError("truncated checkpoint stream")
        arr = np.frombuffer(
            data, dtype=_resolve_dtype(entry["dtype"]), count=count,
            offset=body_start + entry["offset"],
        ).reshape(entry["shape"])
        if device_put_fn is not None:
            out_leaves.append(device_put_fn(arr, tleaf))
        else:
            out_leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def device_put_like(arr: np.ndarray, target_leaf: Any,
                    copy: bool = True) -> Any:
    """Place ``arr`` with the same sharding/device as ``target_leaf``.
    ``copy=False`` hands ``arr``'s own memory to the transfer when the
    dtypes agree (no host copy first): for a caller that writes to it
    again only after the placed array is ready, and never on a backend
    that may keep the memory as the array's own."""
    if isinstance(target_leaf, jax.Array):
        return jax.device_put(arr.astype(target_leaf.dtype, copy=copy),
                              target_leaf.sharding)
    return arr
