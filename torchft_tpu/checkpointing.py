"""Live-weight checkpoint transfer for healing.

Each worker runs a :class:`CheckpointServer`: a daemon-threaded HTTP server
streaming the **live** state pytree for ``GET /checkpoint/{step}`` — state is
produced lazily inside the request handler, no disk involved, exactly like the
reference (/root/reference/torchft/checkpointing.py:50-72 serving
``torch.save(state_dict())`` per request).

Consistency comes from step gating (reference ``checkpointing.py:123-144``):
the Manager opens the window with :meth:`allow_checkpoint` at step start
(while compute runs) and shuts it with :meth:`disallow_checkpoint` at commit,
so a healer can never observe a half-updated state.

TPU-native differences from the reference:

* The payload is the :mod:`torchft_tpu.serialization` pytree format (no
  pickle — a malicious peer cannot execute code on the healer, unlike
  ``torch.load``), and restore goes through ``jax.device_put`` with the
  healer's own shardings.
* **The donor never stalls at commit.** The reference holds its serve lock
  for the entire transfer, so ``disallow_checkpoint`` (and with it the
  donor's commit, and its training) blocks until every in-flight healer
  download finishes — up to the full send timeout
  (/root/reference/torchft/checkpointing.py:123-144). Here the first GET of
  a step captures an **on-device snapshot** of the state under the lock
  (``jnp.copy`` per jax leaf — one pass at HBM bandwidth, milliseconds) and
  streams from the snapshot with no lock held. ``jax.Array`` immutability
  makes the snapshot consistent forever; the copy (rather than a bare
  reference) is what makes it survive the commit-time optimizer update,
  which *donates* the old params/opt-state buffers to XLA
  (optim.py ``donate_argnums``) — a donated array is deleted even while
  other references exist. Commit therefore proceeds concurrently with any
  number of slow healer downloads. The price is one transient state-sized
  copy in HBM while a heal is being served (it goes when the last stream
  reading it ends, or at commit); for donors too memory-tight for
  that, ``lock_streaming=True`` restores the reference's
  hold-the-lock-and-wait behavior.
"""

from __future__ import annotations

import functools
import json
import logging
import mmap
import queue
import re
import threading
import time
import urllib.error
import urllib.parse
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple, TypeVar

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu import chaos, transport
from torchft_tpu.retry import RetryError, RetryPolicy, RetryStats
from torchft_tpu.tracing import maybe_span
from torchft_tpu.utils import advertise_host
from torchft_tpu.serialization import (
    DEFAULT_BATCH_BYTES,
    DEFAULT_CHUNK_BYTES,
    StageClock,
    _is_array_leaf,
    _leaf_nbytes,
    _match_entries,
    _read_exact_into,
    _resolve_dtype,
    balanced_ranges,
    device_put_like,
    load_pytree_from,
    manifest_from,
    plan_pytree,
)

T = TypeVar("T")
logger: logging.Logger = logging.getLogger(__name__)

MANIFEST_SUFFIX = "/manifest"
MANIFEST_FORMAT = "tft-manifest-1"
# Re-fetch budget per leaf before a digest mismatch is declared
# persistent (donor-side corruption, not corruption in transit) and the
# heal fails loudly instead of looping.
MAX_LEAF_REFETCHES = 3
# The healer's stages, as ``load_from_address``'s ``stats`` (``<stage>_ms``)
# and ``Manager.metrics()`` (``heal_<stage>_ms_total``) name them.
HEAL_STAGES = ("manifest", "recv", "verify", "place")


class HealCorruptError(ValueError):
    """A leaf's digest mismatched on every re-fetch: the donor's copy
    itself is corrupt (or the manifest lies). Fatal — retrying the same
    donor cannot help; a failover to another donor can."""


class LeafDigestError(ValueError):
    """One or more leaves failed digest verification in transit.
    Transient: the bytes were corrupted on the wire, a re-fetch is the
    fix (bounded per leaf by ``MAX_LEAF_REFETCHES``)."""


# Request-side Content-Range of a RAM-tier replication PUT:
# ``bytes <start>-<end>/<total>`` (no wildcard forms — a pusher always
# knows its image size).
_CONTENT_RANGE_RE = re.compile(r"bytes (\d+)-(\d+)/(\d+)$")


# The server-body, Range-negotiation, auth, pooling, and byte-counting
# machinery now lives in the transport substrate
# (:mod:`torchft_tpu.transport`) — ONE implementation shared with the
# publication tier, the RAM tier, and the parameter server. The
# underscore aliases keep this module's historical surface (tests and
# serving.py import them from here).
_check_bearer_auth = transport.check_bearer_auth
_negotiate_range = transport.negotiate_range
_serve_ranged_body = transport.serve_ranged_body
_serve_ranged_bytes = transport.serve_ranged_bytes


def build_manifest(plan: Any, step: int,
                   clock: Optional[StageClock] = None) -> dict:
    """JSON transfer manifest for one serialized snapshot: the header's
    leaf entries (array entries annotated with ``offset``/``nbytes``
    body coordinates and a ``crc32`` content digest) plus the stream
    geometry a resuming healer needs (``preamble_len``, ``total_len``).
    Digests come from :meth:`PytreePlan.digests` — computed once per
    snapshot, cached, shared by every healer. The digest/geometry core
    is :func:`torchft_tpu.serialization.manifest_from`, shared with the
    durable on-disk checkpoint trailer
    (:mod:`torchft_tpu.checkpoint_io`). ``clock`` takes the digest
    pass's D2H time (``fetch``)."""
    return {
        "format": MANIFEST_FORMAT,
        "step": int(step),
        **manifest_from(plan, plan.digests(clock=clock)),
    }


_open_url = transport.open_url
_PooledResponse = transport.PooledResponse
_ConnectionPool = transport.ConnectionPool
_CountingReader = transport.CountingReader


def _heal_endpoint(addr: str) -> str:
    """Per-donor chaos endpoint (``heal:<host:port>``): donor-kill
    faults latch a single donor dead while the ``heal`` channel's config
    and RNG stream stay shared across donors."""
    netloc = urllib.parse.urlparse(addr).netloc
    return f"heal:{netloc}" if netloc else "heal"


# Heal-domain entries in the shared classification table
# (:func:`torchft_tpu.transport.classify`): in-transit digest
# mismatches re-fetch (transient); a donor whose own copy is corrupt
# does not (fatal — failover can help, retrying cannot). The 503
# serve-window / shutting-down HTTP rule lives in the table itself.
transport.register_fatal(HealCorruptError)
transport.register_transient(LeafDigestError)


def _heal_transient(exc: BaseException) -> bool:
    """Heal retryability — a delegating alias of THE shared
    classification table (:func:`torchft_tpu.transport.classify`): 503
    "serve window closed (commit)" is transient BY CONSTRUCTION — the
    donor reopens the window at its next step start — while step/auth
    refusals (400/401) and shutdown stay fatal; in-transit digest
    mismatches re-fetch, persistent ones (:class:`HealCorruptError`)
    don't."""
    return transport.classify(exc)


_looks_donor_dead = transport.looks_peer_dead


class _StagingPool:
    """Host buffers of one heal transfer: where a leaf waits, whole, from
    its first byte off the socket until it has verified and been placed
    (a leaf's crc32 is known only with its last byte, and nothing of an
    unverified leaf may reach the device). It is the transfer's bound on
    host memory: the buffers lent out and the ones kept idle never hold
    more than ``bound`` bytes together, and :meth:`take` waits beyond it
    for one to come back.

    A buffer whose leaf has been copied to a device comes back and is
    lent again to the next leaf of its size (a model's layers repeat
    their shapes; its embedding and its head are one shape): pages that
    were written once, where a new mapping of such a size is
    never-touched pages that fault in under ``recv_into`` at a third of
    the socket's speed (PERF.md, PR 46). A leaf that stays on the host
    (``device_put=False``; the CPU backend, which may alias what it is
    given) keeps its buffer, and the buffer leaves the pool's count: it
    is the result then, not in flight."""

    def __init__(self, bound: int) -> None:
        self.bound = int(bound)
        self.peak = 0                       # most bytes ever held at once
        self._held = 0                      # lent + idle + being warmed
        self._idle: Dict[int, List[np.ndarray]] = {}
        self._warming: Dict[int, int] = {}
        self._cond = threading.Condition()

    def _hold(self, nbytes: int) -> None:
        self._held += nbytes
        self.peak = max(self.peak, self._held)

    def warm(self, nbytes: int) -> None:
        """Have one buffer of ``nbytes`` written once before its first
        use, on a thread of its own: the healer does this for its
        largest leaf while it waits for the donor's manifest, so the
        page faults run beside the donor's digest pass and not inside
        the stream."""
        with self._cond:
            self._hold(nbytes)
            self._warming[nbytes] = self._warming.get(nbytes, 0) + 1

        def touch() -> None:
            buf = None
            try:
                buf = np.empty(nbytes, np.uint8)
                buf[::mmap.PAGESIZE] = 0
            finally:
                with self._cond:
                    self._warming[nbytes] -= 1
                    if buf is None:
                        self._held -= nbytes
                    else:
                        self._idle.setdefault(nbytes, []).append(buf)
                    self._cond.notify_all()

        threading.Thread(target=touch, name="heal-warm",
                         daemon=True).start()

    def take(self, nbytes: int) -> np.ndarray:
        """A uint8 buffer of exactly ``nbytes``: an idle one of that
        size, else a new one once the bound allows it (idle buffers of
        other sizes are let go first)."""
        with self._cond:
            while True:
                idle = self._idle.get(nbytes)
                if idle:
                    return idle.pop()
                if not self._warming.get(nbytes):
                    for size in sorted(self._idle, reverse=True):
                        bufs = self._idle[size]
                        while bufs and self._held + nbytes > self.bound:
                            bufs.pop()
                            self._held -= size
                    if self._held + nbytes <= self.bound:
                        self._hold(nbytes)
                        break
                self._cond.wait()
        return np.empty(nbytes, np.uint8)

    def give(self, buf: np.ndarray, reusable: bool) -> None:
        """Hand a taken buffer back: to be lent again, or (not
        ``reusable``: someone else holds its memory now) out of the
        count."""
        with self._cond:
            if reusable:
                self._idle.setdefault(len(buf), []).append(buf)
            else:
                self._held -= len(buf)
            self._cond.notify_all()


def _left_host(placed: Any) -> bool:
    """Wait until a placed leaf's copy has finished; True when it went to
    a device with memory of its own, so that the host buffer it was read
    from may be written again. The CPU backend may keep the buffer it was
    handed as the array's own memory, and a leaf that was not placed at
    all is the buffer."""
    if not isinstance(placed, jax.Array):
        return False
    placed.block_until_ready()
    return all(d.platform != "cpu" for d in placed.devices())


class _LeafPipeline:
    """The verify and place stages of one Range fetch, a thread each,
    behind the reader (the calling thread, which reads the socket into
    the leaf's staging buffer a chunk at a time):

    * verify: ``zlib.crc32`` over each chunk as it lands, folded into
      the leaf's running digest, so the verdict is there with the leaf's
      last byte. A mismatch leaves the leaf missing (counted, bounded by
      ``MAX_LEAF_REFETCHES``) and its buffer goes back unplaced;
    * place: a verified leaf's ``session.commit`` (``device_put``), the
      wait for its copy, ``progress_cb``; then the buffer goes back.

    The three run beside each other: chunk k+1 is on the wire while
    chunk k is digested and the leaf before is copied to the device.
    What cannot hide is the last leaf's last chunk's digest and that
    leaf's placement. Order is the stream's, so progress is monotone. A
    stage that fails latches :attr:`error`; the reader sees it at its
    next chunk, and every leaf already verified is still placed or its
    buffer returned: nothing committed is lost, nothing is left lent."""

    def __init__(self, session: "_HealSession", donor: str,
                 progress_cb: Optional[Callable[[int, int], None]]
                 ) -> None:
        self._session = session
        self._donor = donor
        self._progress_cb = progress_cb
        self.error: Optional[BaseException] = None
        self._verify_q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._place_q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._threads = [
            threading.Thread(target=self._run, name=f"heal-{stage}",
                             args=(stage, work), daemon=True)
            for stage, work in (("verify", self._verify),
                                ("place", self._place))]
        for t in self._threads:
            t.start()

    def landed(self, i: int, buf: np.ndarray, off: int, n: int) -> None:
        """Bytes ``[off, off + n)`` of leaf ``i`` are in ``buf``; with
        the first call the buffer is the pipeline's to give back."""
        self._verify_q.put((i, buf, off, n))

    def close(self) -> None:
        """No more chunks: let the stages finish what they hold."""
        self._verify_q.put(None)
        for t in self._threads:
            t.join()

    def _run(self, stage: str,
             work: Callable[[], Tuple[float, int]]) -> None:
        with self._session.span(f"heal_{stage}", donor=self._donor) as sp:
            busy_s, leaves = work()
            sp.set(busy_ms=round(busy_s * 1e3, 3), leaves=leaves)
        self._session.clock.add(stage, busy_s)

    def _fail(self, exc: BaseException) -> None:
        if self.error is None:
            self.error = exc

    def _verify(self) -> Tuple[float, int]:
        session, staging = self._session, self._session.staging
        open_buf: Optional[np.ndarray] = None   # a leaf partly landed
        crc = 0
        busy_s, leaves = 0.0, 0
        try:
            while True:
                msg = self._verify_q.get()
                if msg is None:
                    break
                i, buf, off, n = msg
                last = off + n == len(buf)
                open_buf = None if last else buf
                if self.error is not None:
                    if last:
                        staging.give(buf, True)
                    continue
                try:
                    t0 = time.perf_counter()
                    crc = zlib.crc32(memoryview(buf)[off:off + n],
                                     crc if off else 0)
                    busy_s += time.perf_counter() - t0
                    if not last:
                        continue
                    leaves += 1
                    entry = session.pairs[i][0]
                    if "crc32" not in entry or crc == int(entry["crc32"]):
                        self._place_q.put((i, buf, crc))
                        continue
                    staging.give(buf, True)
                    with session.lock:
                        session.digest_mismatches += 1
                        tries = session.refetches[i] = \
                            session.refetches.get(i, 0) + 1
                    logger.warning(
                        "heal: leaf %r digest mismatch "
                        "(got %08x, manifest %08x; refetch %d/%d)",
                        entry["key"], crc, int(entry["crc32"]), tries,
                        MAX_LEAF_REFETCHES)
                    if tries >= MAX_LEAF_REFETCHES:
                        self._fail(HealCorruptError(
                            f"leaf {entry['key']!r} failed digest "
                            f"verification {tries} times; the donor's "
                            "copy is corrupt"))
                    # else it stays missing; the next round re-spans it
                except BaseException as e:  # noqa: BLE001 — to the reader
                    self._fail(e)
                    if last:
                        staging.give(buf, True)
        finally:
            if open_buf is not None:    # the stream ended inside a leaf
                staging.give(open_buf, True)
            self._place_q.put(None)
        return busy_s, leaves

    def _place(self) -> Tuple[float, int]:
        session, staging = self._session, self._session.staging
        busy_s, leaves = 0.0, 0
        while True:
            msg = self._place_q.get()
            if msg is None:
                return busy_s, leaves
            i, buf, crc = msg
            if self.error is not None:
                staging.give(buf, True)
                continue
            reusable = False
            try:
                t0 = time.perf_counter()
                entry = session.pairs[i][0]
                placed = session.commit(
                    i, buf.view(_resolve_dtype(entry["dtype"])).reshape(
                        entry["shape"]), crc, donor=self._donor)
                reusable = _left_host(placed)
                busy_s += time.perf_counter() - t0
                leaves += 1
                if self._progress_cb is not None:
                    self._progress_cb(session.committed_bytes,
                                      session.total_len)
            except BaseException as e:  # noqa: BLE001 — to the reader
                self._fail(e)
            finally:
                staging.give(buf, reusable)


class _HealSession:
    """Cross-attempt, cross-donor state of one resumable heal transfer:
    which leaves are committed (digest-verified and placed), their
    verified digests (the cross-donor identity check), and the truthful
    byte counters. Survives transport failures and donor failovers; a
    fresh attempt re-enters at the first missing leaf."""

    def __init__(self, target: Any,
                 device_put_fn: Optional[Callable]) -> None:
        self.target = target
        self.device_put_fn = device_put_fn
        # Host memory of the transfer, beyond what it returns: the leaf
        # on the wire and the leaves being verified and placed, at most
        # twice the target's widest leaf (or two fetch batches) in all.
        self.widest = max(
            [_leaf_nbytes(leaf)
             for leaf in jax.tree_util.tree_leaves(target)
             if _is_array_leaf(leaf)] + [0])
        self.staging = _StagingPool(
            2 * max(self.widest, DEFAULT_BATCH_BYTES))
        # Busy time of the healer's stages (manifest, recv, verify,
        # place), over every attempt and donor.
        self.clock = StageClock()
        self.treedef: Any = None
        self.pairs: Optional[list] = None   # [(entry, target_leaf)]
        self.arr_order: List[int] = []      # array pair indices, body order
        self.committed: Dict[int, Any] = {}
        self.crcs: Dict[int, int] = {}      # verified crc32 per pair idx
        self.refetches: Dict[int, int] = {}
        self.preamble_len = 0
        self.total_len = 0
        self.committed_bytes = 0
        self.bytes_read = 0
        self.bytes_resumed = 0
        self.rounds = 0                     # data fetch rounds (attempts)
        self.failovers = 0
        self.digest_mismatches = 0
        # Striped mode: donors that actually landed committed leaves,
        # and the lock making commit/byte accounting safe under the
        # per-donor fetch threads (single-donor fetches never contend).
        self.donors_used: set = set()
        self.stripe_deaths = 0              # striped donors dropped dead
        self.lock = threading.Lock()
        # Persistent per-donor connections shared by every attempt of
        # this transfer: Range waves stop paying a TCP dial per span.
        self.pool = _ConnectionPool()
        # Optional span tracer (torchft_tpu.tracing): each donor's
        # Range fetch records a `heal_stripe` span, so a striped heal's
        # per-donor concurrency and stragglers are visible on the
        # step timeline.
        self.tracer: Optional[Any] = None

    def span(self, stage: str, **tags: Any) -> Any:
        return maybe_span(self.tracer, stage, **tags)

    def adopt_manifest(self, mf: dict, expect_changes: bool = False
                       ) -> None:
        """Validate a donor's manifest against the target (structure,
        shapes, dtypes — the same untrusted-header discipline as the
        byte stream) and reconcile committed progress: leaves stay
        committed iff the new manifest's digest matches the one we
        verified. By default a mismatch is a VIOLATION of the same-step
        bitwise-identity invariant (a heal failover to another donor of
        the same step) — loud, and counted in ``digest_mismatches``.
        ``expect_changes=True`` is the delta-publication mode
        (:mod:`torchft_tpu.serving`): the manifest describes a *newer
        generation*, so differing digests are the changed leaves the
        delta fetch exists to re-fetch — dropped quietly, not counted."""
        pairs, treedef = _match_entries({"leaves": mf["leaves"]},
                                        self.target)
        first = self.pairs is None
        self.pairs = pairs
        self.treedef = treedef
        self.arr_order = [i for i, (e, _) in enumerate(pairs)
                          if e["kind"] == "array"]
        self.preamble_len = int(mf["preamble_len"])
        self.total_len = int(mf["total_len"])
        if not first:
            # A fresh donor/generation gets a fresh per-leaf refetch
            # budget: the persistent-mismatch verdict was about the OLD
            # copy. (Re-adopting the SAME manifest is the caller's to
            # avoid — it would reset the budget every round.)
            self.refetches.clear()
            for i in list(self.committed):
                entry = pairs[i][0]
                if entry["kind"] != "array":
                    continue
                want = entry.get("crc32")
                if want is not None and i in self.crcs \
                        and int(want) != self.crcs[i]:
                    if not expect_changes:
                        logger.warning(
                            "heal: cross-donor digest mismatch on leaf "
                            "%r (had %08x, new donor claims %08x) — "
                            "same-step snapshots should be bitwise "
                            "identical; re-fetching it from the new "
                            "donor",
                            entry["key"], self.crcs[i], int(want))
                        self.digest_mismatches += 1
                    del self.committed[i]
                    self.crcs.pop(i, None)
                    self.committed_bytes -= int(entry["nbytes"])
        # py leaves and zero-byte arrays commit straight off the
        # manifest — no wire bytes to wait for.
        for i, (entry, tleaf) in enumerate(pairs):
            if i in self.committed:
                continue
            if entry["kind"] == "py":
                self.committed[i] = entry["value"]
            elif int(entry["nbytes"]) == 0:
                arr = np.empty(entry["shape"],
                               _resolve_dtype(entry["dtype"]))
                self.commit(i, arr, zlib.crc32(b""))

    def commit(self, i: int, arr: np.ndarray, crc: int,
               donor: Optional[str] = None) -> Any:
        tleaf = self.pairs[i][1]
        placed = (self.device_put_fn(arr, tleaf)
                  if self.device_put_fn is not None else arr)
        with self.lock:
            self.committed[i] = placed
            self.crcs[i] = crc
            self.committed_bytes += int(self.pairs[i][0]["nbytes"])
            if donor is not None:
                self.donors_used.add(donor)
        return placed

    def note_bytes(self, n: int) -> None:
        with self.lock:
            self.bytes_read += n
            if self.rounds > 1:
                self.bytes_resumed += n

    def missing(self) -> List[int]:
        with self.lock:
            return [i for i in self.arr_order if i not in self.committed]

    def complete(self) -> bool:
        return (self.pairs is not None
                and len(self.committed) == len(self.pairs))

    def spans_for(self, idxs: List[int]) -> List[list]:
        """Coalesce leaf indices (body order) into contiguous ``[start,
        end, [pair indices]]`` byte spans (absolute stream offsets), one
        Range request each."""
        out: List[list] = []
        for i in idxs:
            entry = self.pairs[i][0]
            a = self.preamble_len + int(entry["offset"])
            b = a + int(entry["nbytes"])
            if out and out[-1][1] == a:
                out[-1][1] = b
                out[-1][2].append(i)
            else:
                out.append([a, b, [i]])
        return out

    def spans(self) -> List[list]:
        """Missing leaves as coalesced spans — the first attempt is a
        single span covering the whole body; later attempts cover only
        what's left."""
        return self.spans_for(self.missing())

    def stripes(self, n: int) -> List[List[int]]:
        """Partition the missing leaves into ``n`` contiguous,
        byte-balanced groups (group ``k`` for donor ``k``; may be empty
        when little is left). Contiguity keeps each donor's fetch a
        handful of coalesced Range requests instead of a shotgun of
        per-leaf ones."""
        missing = self.missing()
        sizes = [int(self.pairs[i][0]["nbytes"]) for i in missing]
        return [missing[a:b] for a, b in balanced_ranges(sizes, n)]

    def assemble(self) -> Any:
        leaves = [self.committed[i] for i in range(len(self.pairs))]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


# One jitted call copying a whole list of arrays: per-leaf EAGER copies
# would pay a dispatch (and first-time compile) per leaf, while one
# compiled program runs at HBM bandwidth and its executable caches per state structure. Without
# donation XLA cannot alias inputs to outputs, so these are real copies.
_copy_leaves = jax.jit(lambda leaves: [jnp.copy(leaf) for leaf in leaves])


def _snapshot_tree(tree: Any) -> Any:
    """A copy that stays valid after the commit-time donated update.

    Only jax leaves need copying (donation deletes them even while other
    references exist); the copy is on-device, sharding-preserving, and runs
    at HBM bandwidth in a single dispatch. numpy/scalar leaves pass by
    reference — host RAM stays O(leaf) for large host-side states, and the
    FT commit contract REPLACES pytrees rather than mutating leaves in
    place, so a served reference stays consistent."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    jax_idx = [i for i, leaf in enumerate(leaves)
               if isinstance(leaf, jax.Array)]
    if jax_idx:
        copied = _copy_leaves([leaves[i] for i in jax_idx])
        for i, c in zip(jax_idx, copied):
            leaves[i] = c
    return jax.tree_util.tree_unflatten(treedef, leaves)


class CheckpointServer:
    """Serves the live state pytree to healing peers, step-gated.

    Args:
        state_fn: zero-arg callable returning the current state pytree.
            Called lazily inside the first GET handler of a step, under the
            serve lock.
        send_timeout_sec: per-socket-write timeout while streaming (bounds a
            hung healer), and the bound on how long a GET waits for a closed
            serve window to reopen.
        lock_streaming: serve the **live** state under the serve lock for
            the whole transfer (reference behavior: commit blocks until
            in-flight downloads finish). Only for donors too memory-tight
            for the default snapshot copy.
        bind_host: interface to listen on. Default binds all interfaces
            like the reference (checkpointing.py serves 0.0.0.0); set to an
            internal/VPC address on shared networks — this server streams
            full model weights to anyone who can connect.
        bind_port: port to listen on (default 0 = OS-assigned). A churn
            replacement can pin its predecessor's port so advertised
            addresses stay dialable across the respawn.
        auth_token: when set, every GET must carry
            ``Authorization: Bearer <token>`` or is refused with 401.
            Healers send it automatically when the Manager is constructed
            with the same token (``TORCHFT_AUTH_TOKEN``).
    """

    def __init__(self, state_fn: Callable[[], T],
                 send_timeout_sec: float = 120.0,
                 lock_streaming: bool = False,
                 bind_host: str = "0.0.0.0",
                 auth_token: Optional[str] = None,
                 bind_port: int = 0) -> None:
        self._state_fn = state_fn
        self._send_timeout_sec = send_timeout_sec
        self._lock_streaming = lock_streaming
        self._auth_token = auth_token
        self._bind_host = bind_host
        # One condition guards the tiny critical sections: the step window,
        # the snapshot cache, and the in-flight stream count.
        self._cond = threading.Condition()
        self._allowed = True
        self._step = -1
        self._inflight = 0
        self._shutdown = False
        # (step, state, plan): snapshot shared by every GET of the same
        # step, so N concurrent healers cost one copy, not N.
        self._snap: Optional[Tuple[int, Any, Any]] = None
        # Attached live-publication store (torchft_tpu.serving): serves
        # /publish/* generations through this same server — published
        # snapshots are immutable, so they are NOT step-gated by the
        # heal serve window (a commit in progress never blocks them).
        self._publication: Optional[Any] = None
        # Attached observability exports (torchft_tpu.tracing,
        # docs/design/observability.md): GET /trace.json (Chrome trace
        # events from the span ring) and GET /metrics (Prometheus text
        # exposition) on the same socket + auth gate. Snapshot reads of
        # immutable/locked state — like /publish, never step-gated.
        self._obs: Optional[Dict[str, Any]] = None
        # Attached RAM checkpoint store (torchft_tpu.ram_ckpt,
        # docs/design/memory_tier.md): serves stored peer images at
        # /ramckpt/* and accepts replication PUTs. Images are immutable
        # and pre-verified — like /publish, never step-gated.
        self._ram_store: Optional[Any] = None
        # Divergence-verdict serve gate (set_quarantined,
        # docs/design/state_attestation.md): sticky 503 on every
        # state-serving GET while the owning Manager is quarantined.
        self._quarantined = False
        # Busy time of this donor's heal stages: ``fetch`` (D2H of the
        # digest pass and of every stream), ``send`` (socket writes).
        self._clock = StageClock()

        # Host on the transport substrate's shared server core (async
        # event loop by default, TORCHFT_ASYNC_SERVER=0 for the legacy
        # threaded host) — the route body below is the same on either.
        self._server = transport.serve_http(bind_host, bind_port,
                                            self._route,
                                            name="checkpoint-server")
        # A fresh server at this address is a REBIRTH for the chaos kill
        # latches: a churn replacement reusing a dead member's host:port
        # must not inherit the corpse's dead latch (chaos.endpoint_reborn
        # is a no-op without an active schedule).
        netloc = urllib.parse.urlparse(self.address()).netloc
        if netloc:
            chaos.endpoint_reborn(f"heal:{netloc}", f"serve:{netloc}",
                                  f"ram:{netloc}")

    def _route(self, handler: Any) -> None:
        """One request on the substrate core (GET heal/manifest/
        publication/RAM/observability, PUT RAM replication). Keep-alive:
        healers and weight subscribers reuse one connection across Range
        waves (``transport.ConnectionPool``); every response path sends
        Content-Length, which HTTP/1.1 persistence requires."""
        if handler.command == "PUT":
            self._route_put(handler)
            return
        if handler.command != "GET":
            handler.send_error(501, "Unsupported method "
                               f"({handler.command!r})")
            return
        if not _check_bearer_auth(handler, self._auth_token):
            return
        if handler.path.split("?", 1)[0].rstrip("/") in (
                "/trace.json", "/metrics"):
            if self._shutdown:
                handler.close_connection = True
                return
            self._serve_observability(handler)
            return
        if self._quarantined:
            # Divergence verdict latched on the owning Manager: every
            # byte this server could hand out (heal stream, RAM image,
            # published generation) came from state the fleet voted
            # divergent. Refuse hard — a peer holding our cached
            # address rotates to an attested donor — while
            # observability above stays up for the operator reading
            # the verdict. PUTs stay open: images stored FOR peers are
            # theirs, not ours.
            handler.send_error(503, "quarantined (divergence verdict)")
            return
        if handler.path.split("?", 1)[0].rstrip("/") == "/publish" \
                or handler.path.startswith("/publish/"):
            if self._shutdown:
                # Drop kept-alive connections like a dead process
                # would: subscribers re-dial and reach the restarted
                # server on this port, instead of a zombie handler
                # serving stale generations.
                handler.close_connection = True
                return
            pub = self._publication
            if pub is None:
                handler.send_error(404, "no publication attached")
                return
            pub.handle_request(
                handler, send_timeout_sec=self._send_timeout_sec)
            return
        if handler.path.startswith("/ramckpt/"):
            # RAM-tier images are immutable and pre-verified: NOT
            # step-gated by the heal serve window — a commit in
            # progress never blocks a replacement healing from
            # yesterday's committed image.
            if self._shutdown:
                handler.close_connection = True
                return
            self._serve_ram(handler)
            return
        prefix = "/checkpoint/"
        if not handler.path.startswith(prefix):
            handler.send_error(404, "unknown path")
            return
        path = handler.path
        want_manifest = path.endswith(MANIFEST_SUFFIX)
        if want_manifest:
            path = path[:-len(MANIFEST_SUFFIX)]
        try:
            req_step = int(path[len(prefix):])
        except ValueError:
            handler.send_error(400, "bad step")
            return
        deadline = time.monotonic() + self._send_timeout_sec
        with self._cond:
            # A closed window (commit in progress) reopens at the
            # next step start; park briefly rather than bouncing
            # the healer (the reference blocks here too, on its
            # held lock).
            while not self._allowed and not self._shutdown:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    handler.send_error(
                        503, "serve window closed (commit)")
                    return
                self._cond.wait(timeout=remaining)
            if self._shutdown:
                handler.send_error(503, "shutting down")
                return
            if req_step != self._step:
                handler.send_error(
                    400,
                    f"invalid checkpoint requested: serving "
                    f"{self._step} but got {req_step}")
                return
            if want_manifest and self._lock_streaming:
                # Live lock-streamed state has no immutable snapshot
                # to digest; healers fall back to the legacy
                # (non-resumable) full-stream fetch.
                handler.send_error(
                    404, "manifest unavailable (lock_streaming "
                    "serves live state)")
                return
            try:
                state, plan = self._capture_locked()
            except Exception as e:  # surface to healer, keep serving
                logger.exception("checkpoint state capture failed")
                handler.send_error(500, str(e))
                return
            self._inflight += 1
        # Stream OUTSIDE the lock: the snapshot is immutable, so a
        # slow healer never delays the donor's commit. Leaf-by-leaf:
        # total length is known from the plan before any device data
        # is fetched, so the response carries Content-Length yet
        # never holds more than one leaf + one chunk in host RAM;
        # socket-write backpressure paces the device_get fetches.
        try:
            if want_manifest:
                # Digest pass runs outside the serve lock too (the
                # snapshot is immutable); computed once per snapshot,
                # shared by every healer and attempt.
                body = json.dumps(
                    build_manifest(plan, req_step, self._clock)).encode()
                handler.send_response(200)
                handler.send_header("Content-Type", "application/json")
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.connection.settimeout(self._send_timeout_sec)
                handler.wfile.write(body)
                return
            # Once the status line is committed, a device_get failure
            # mid-stream can only short-close the socket (healer sees
            # "truncated"), so log the real cause here.
            try:
                _serve_ranged_body(handler, state, plan,
                                   self._send_timeout_sec, self._clock)
            except Exception:
                logger.exception(
                    "checkpoint stream failed mid-transfer "
                    "(healer will see a truncated stream)")
                raise
        finally:
            with self._cond:
                self._inflight -= 1
                if (self._inflight == 0 and not want_manifest
                        and not self._lock_streaming):
                    # The last stream reading the snapshot has ended. Let
                    # the copy go now, not at the donor's commit: a donor
                    # that waits in the ring for its healer would carry a
                    # dead copy of its whole state under its gradients.
                    # The window is still open and the live state is the
                    # same pre-update one, so a later GET of this step
                    # snapshots the same bytes again.
                    self._snap = None
                self._cond.notify_all()

    def _route_put(self, handler: Any) -> None:
        # The RAM tier's push-side replication: ranged writes of a
        # peer's v2 image against /ramckpt/{step}
        # (docs/design/memory_tier.md). The assembled image is
        # digest-verified BEFORE acceptance; a failed scan is a 422
        # and nothing is stored.
        if not _check_bearer_auth(handler, self._auth_token):
            return
        if self._shutdown:
            handler.close_connection = True
            return
        self._accept_ram_push(handler)

    def set_quarantined(self, flag: bool) -> None:
        """Sticky divergence-verdict serve gate
        (docs/design/state_attestation.md): while set, every
        state-serving GET (``/checkpoint/*``, ``/ramckpt/*``,
        ``/publish/*``) refuses with 503, so a peer that cached this
        server's address cannot fetch bytes the fleet voted divergent
        through ANY route. Cleared when the lighthouse confirms the
        re-attested digest (Manager's verdict-clear path)."""
        with self._cond:
            self._quarantined = bool(flag)
            self._cond.notify_all()

    def _capture_locked(self) -> Tuple[Any, Any]:
        """State + plan to stream for the current step. Requires _cond held.

        ONE ``(state, plan)`` pair is cached per serve window in BOTH
        modes and shared by every concurrent manifest/Range request of
        the step — so striped healers fanning N Range fetches at one
        donor share a single :class:`~torchft_tpu.serialization.
        PytreePlan` and its once-computed digest cache instead of
        re-planning (and re-digesting) per request. Snapshot mode: the
        first GET of the step copies the state (see module docstring).
        Lock-streaming mode: the cache holds LIVE refs — safe because
        ``disallow_checkpoint`` drains in-flight streams and clears the
        cache before the caller mutates state."""
        if self._snap is None or self._snap[0] != self._step:
            state = (self._state_fn() if self._lock_streaming
                     else _snapshot_tree(self._state_fn()))
            self._snap = (self._step, state, plan_pytree(state))
        return self._snap[1], self._snap[2]

    def metrics(self) -> Dict[str, float]:
        """This donor's heal stages, busy ms since it started (merged
        into ``Manager.metrics()``)."""
        return {"heal_serve_fetch_ms_total": self._clock.ms("fetch"),
                "heal_serve_send_ms_total": self._clock.ms("send")}

    def address(self) -> str:
        """Dialable HTTP URL for the current step's checkpoint. When bound
        to a specific interface, that address is what peers can actually
        reach — advertising the hostname's primary interface would hand
        healers a connection-refused URL."""
        port = self._server.server_address[1]
        host = (self._bind_host
                if self._bind_host not in ("", "0.0.0.0", "::")
                else advertise_host())
        if ":" in host:  # bare IPv6 literals need brackets in URLs
            host = f"[{host}]"
        return f"http://{host}:{port}/checkpoint/{self._step}"

    def attach_observability(self, tracer: Any = None,
                             metrics_fn: Optional[Callable[[], Dict]]
                             = None,
                             info_fn: Optional[Callable[[], Dict]]
                             = None,
                             labels: Optional[Dict[str, str]]
                             = None) -> None:
        """Attach the observability exports
        (docs/design/observability.md): ``tracer`` (a
        :class:`torchft_tpu.tracing.Tracer`) backs ``GET
        /trace.json?steps=K`` — the span ring of the last K steps in
        Chrome trace-event format, Perfetto-loadable and the fleet
        merger's input — and ``metrics_fn``/``info_fn`` (the Manager's
        ``metrics``/``metrics_info``) back ``GET /metrics``, Prometheus
        text exposition with ``labels`` on every sample. The Manager
        attaches its own at construction."""
        self._obs = {"tracer": tracer, "metrics_fn": metrics_fn,
                     "info_fn": info_fn, "labels": dict(labels or {})}

    def _serve_observability(self, handler: Any) -> None:
        """Serve one /trace.json or /metrics GET (auth already
        checked). Snapshot reads only — never step-gated, never blocks
        a commit."""
        from torchft_tpu import tracing as tracing_mod

        obs = self._obs
        path, _, query = handler.path.partition("?")
        path = path.rstrip("/")
        try:
            if path == "/trace.json":
                tracer = obs.get("tracer") if obs else None
                if tracer is None:
                    handler.send_error(404, "no tracer attached")
                    return
                qs = urllib.parse.parse_qs(query)
                steps = None
                if "steps" in qs:
                    # 400 only for the client's parse error — a
                    # ValueError from deeper (a metrics/trace snapshot
                    # racing shutdown) must stay a logged 500, not be
                    # misattributed to the request.
                    try:
                        steps = max(int(qs["steps"][0]), 1)
                    except ValueError:
                        handler.send_error(400, "bad steps parameter")
                        return
                # default=str: span tags are open-ended; an exotic tag
                # value degrades to its repr instead of a 500.
                body = json.dumps(tracer.chrome_trace(steps),
                                  default=str).encode()
                ctype = "application/json"
            else:  # /metrics
                metrics_fn = obs.get("metrics_fn") if obs else None
                if metrics_fn is None:
                    handler.send_error(404, "no metrics attached")
                    return
                info_fn = obs.get("info_fn")
                body = tracing_mod.prometheus_text(
                    metrics_fn(),
                    info_fn() if info_fn is not None else None,
                    obs.get("labels")).encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
        except Exception as e:  # noqa: BLE001 — surface, keep serving
            logger.exception("observability endpoint failed")
            handler.send_error(500, str(e))
            return
        handler.send_response(200)
        handler.send_header("Content-Type", ctype)
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.connection.settimeout(self._send_timeout_sec)
        handler.wfile.write(body)

    def attach_publication(self, publication: Any) -> None:
        """Attach a live-publication store
        (:class:`torchft_tpu.serving.WeightPublisher`): its generations
        are then served at ``/publish/*`` on this server's port, next to
        the heal endpoints — one socket, one auth gate, two protocols."""
        self._publication = publication

    def detach_publication(self) -> None:
        """Withdraw the publication tier (graceful preemption drain,
        docs/design/churn.md): ``/publish/*`` returns 404 from the next
        request on, which subscribers classify as a dead parent and
        rotate away from — no one is steered at a group that is about
        to exit."""
        self._publication = None

    def publish_address(self) -> str:
        """Dialable base URL of the attached publication tier
        (``…/publish``); hand it to
        :class:`~torchft_tpu.serving.WeightSubscriber` parents."""
        base = self.address()
        return base[:base.rindex("/checkpoint/")] + "/publish"

    def attach_ram_store(self, store: Any) -> None:
        """Attach a :class:`torchft_tpu.ram_ckpt.RamCheckpointStore`:
        its verified images are then served at ``/ramckpt/{step}`` (+
        ``/manifest``, ``/ramckpt/steps``) and peer replication PUTs
        are accepted on this same socket and auth gate — the RAM tier
        rides the existing striped heal transport, no second server."""
        self._ram_store = store

    def detach_ram_store(self) -> None:
        """Withdraw the RAM tier (graceful preemption drain):
        ``/ramckpt/*`` 404s from the next request on, so healers rotate
        to surviving peers instead of a group that is about to exit."""
        self._ram_store = None

    def ram_address(self) -> str:
        """Dialable base URL this server's RAM tier hangs off (append
        ``/ramckpt/{step}``); peers derive the same base from a
        checkpoint address with one ``rsplit`` — no extra registry."""
        base = self.address()
        return base[:base.rindex("/checkpoint/")]

    def _serve_ram(self, handler: Any) -> None:
        """Serve one /ramckpt GET (auth already checked):
        ``/ramckpt/steps`` (stored steps, json), ``/ramckpt/{step}``
        (the image's payload region, ranged — the exact stream a live
        heal serves, so :meth:`load_from_address` works against it
        unchanged), ``/ramckpt/{step}/manifest`` (the heal-protocol
        digest manifest). Never step-gated; a missing image is a plain
        404 the healer turns into falling down the recovery ladder."""
        store = self._ram_store
        if store is None:
            handler.send_error(404, "no RAM checkpoint store attached")
            return
        path = handler.path.split("?", 1)[0].rstrip("/")
        rest = path[len("/ramckpt"):].strip("/")
        try:
            if rest == "steps":
                body = json.dumps({"steps": store.steps()}).encode()
                handler.send_response(200)
                handler.send_header("Content-Type", "application/json")
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.connection.settimeout(self._send_timeout_sec)
                handler.wfile.write(body)
                return
            want_manifest = rest.endswith("/manifest")
            if want_manifest:
                rest = rest[:-len("/manifest")]
            try:
                step = int(rest)
            except ValueError:
                handler.send_error(400, "bad step")
                return
            image = store.get(step)
            if image is None:
                handler.send_error(
                    404, f"no RAM image for step {step}")
                return
            if want_manifest:
                body = json.dumps(image.transfer_manifest()).encode()
                handler.send_response(200)
                handler.send_header("Content-Type", "application/json")
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.connection.settimeout(self._send_timeout_sec)
                handler.wfile.write(body)
                return
            _serve_ranged_bytes(handler, image.payload_view(),
                                self._send_timeout_sec)
        except Exception as e:  # noqa: BLE001 — surface, keep serving
            logger.exception("ram checkpoint serve failed")
            try:
                handler.send_error(500, str(e))
            except Exception:
                pass

    def _accept_ram_push(self, handler: Any) -> None:
        """Accept one replication PUT chunk (auth already checked).
        Status codes: 200 (chunk staged / image accepted — the json
        body's ``complete`` flag says which), 404 (no store attached),
        400 (malformed path/range), 422 (assembled image FAILED digest
        verification — nothing stored), 503 (chaos transport fault on
        the accept path)."""
        from torchft_tpu.checkpoint_io import CheckpointCorruptError

        store = self._ram_store
        if store is None:
            handler.send_error(404, "no RAM checkpoint store attached")
            return
        path = handler.path.split("?", 1)[0].rstrip("/")
        if not path.startswith("/ramckpt/"):
            handler.send_error(404, "unknown path")
            return
        try:
            step = int(path[len("/ramckpt/"):])
        except ValueError:
            handler.send_error(400, "bad step")
            return
        try:
            length = int(handler.headers.get("Content-Length", ""))
        except ValueError:
            handler.send_error(400, "missing Content-Length")
            return
        crng = handler.headers.get("Content-Range")
        if crng is not None:
            m = _CONTENT_RANGE_RE.match(crng.strip())
            if m is None:
                handler.send_error(400, "bad Content-Range")
                return
            start, last, total = (int(m.group(1)), int(m.group(2)),
                                  int(m.group(3)))
            if last - start + 1 != length:
                handler.send_error(
                    400, "Content-Range/Content-Length mismatch")
                return
        else:
            start, total = 0, length
        data = handler.rfile.read(length)
        if len(data) != length:
            handler.send_error(400, "short request body")
            return
        origin = handler.headers.get("X-TFT-Origin", "peer")
        try:
            image = store.stage_write(step, start, data, total,
                                      origin=origin)
        except CheckpointCorruptError as e:
            handler.send_error(422, f"image failed verification: {e}")
            return
        except ValueError as e:
            handler.send_error(400, str(e))
            return
        except (ConnectionError, OSError) as e:
            # The chaos accept hook's transport faults (blackhole /
            # reset / dead peer) — transient to the pusher.
            handler.send_error(503, str(e))
            return
        body = json.dumps({"complete": image is not None}).encode()
        handler.send_response(200)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.connection.settimeout(self._send_timeout_sec)
        handler.wfile.write(body)

    def allow_checkpoint(self, step: int) -> None:
        """Open the serve window for ``step`` (called at step start, while
        the forward/backward runs — the state is still the pre-update
        one)."""
        with self._cond:
            self._step = step
            # Drop a stale-step snapshot (in-flight streams keep their own
            # references; this only frees the cache).
            if self._snap is not None and self._snap[0] != step:
                self._snap = None
            self._allowed = True
            self._cond.notify_all()

    def disallow_checkpoint(self) -> None:
        """Shut the serve window (called at commit).

        Snapshot mode (default): returns immediately — in-flight streams
        serve their immutable snapshot, so commit can donate/replace the
        live state concurrently. Lock-streaming mode: blocks until
        in-flight GETs finish, like the reference."""
        with self._cond:
            self._allowed = False
            self._snap = None
            if self._lock_streaming:
                while self._inflight > 0:
                    self._cond.wait()

    def shutdown(self) -> None:
        with self._cond:
            self._shutdown = True
            self._cond.notify_all()
        self._server.shutdown()
        self._server.server_close()

    @classmethod
    def load_from_address(cls, address: str, target: T,
                          timeout_sec: float = 300.0,
                          device_put: bool = True,
                          stats: Optional[dict] = None,
                          auth_token: Optional[str] = None,
                          retry_policy: Optional[RetryPolicy] = None,
                          retry_stats: Optional[RetryStats] = None,
                          stall_timeout_sec: Optional[float] = None,
                          donors: Optional[Callable[[int], Optional[str]]]
                          = None,
                          max_donor_failovers: int = 3,
                          donor_addrs: Optional[List[str]] = None,
                          stripe_seed: Optional[int] = None,
                          progress_cb: Optional[Callable[[int, int], None]]
                          = None,
                          tracer: Optional[Any] = None) -> T:
        """Fetch a peer's live checkpoint and restore it into ``target``'s
        structure (and shardings, when ``device_put``). Streams, as one
        overlapped pass (:class:`_LeafPipeline`): a leaf is read off the
        socket into a staging buffer while its chunks are digested behind
        the reader and the leaf before it is copied to the device; it is
        ``device_put`` only once its crc32 matched the donor's manifest —
        corrupt or truncated bytes never reach the device. Host memory
        beyond the result: the staging buffers, at most 2 x max(widest
        leaf, 24 MiB) bytes (:class:`_StagingPool`; 1.41 GiB for a state
        whose widest leaf is 723 MiB, where the serial pass held a leaf
        and its copy, the same).

        The transfer is RESUMABLE: the donor's ``/manifest`` endpoint
        describes the stream (per-leaf offsets + crc32 digests), and each
        fetch uses HTTP ``Range`` to re-enter at the first unverified
        leaf, so a transport failure costs O(remaining), not O(state).
        Transient failures (resets, truncation, a 503 while the donor's
        serve window is closed at commit) retry under ``retry_policy``
        with backoff — and because progress is durable, the attempt
        budget bounds *consecutive zero-progress* failures, not total
        failures, so a huge transfer that keeps advancing is never killed
        by an arbitrary deadline. Step/auth refusals (400/401) stay
        fatal. Donors without a manifest (``lock_streaming`` mode, older
        builds) fall back to the legacy whole-stream fetch.

        Liveness comes from a stall watchdog, not a wall clock:
        ``stall_timeout_sec`` bounds how long any single socket operation
        may sit with no bytes arriving (default: ``timeout_sec``, the
        legacy knob). A black-holed stream dies in seconds; a slow but
        moving stream runs forever.

        ``donors``, when given, enables DONOR FAILOVER: when the current
        donor is classified dead (connection refused — its server socket
        is gone — or a persistently corrupt leaf, or the zero-progress
        budget is exhausted), ``donors(failover_index)`` is asked for a
        fresh data URL and the SAME transfer continues there — committed
        leaves are kept iff the new donor's manifest digests match what
        was already verified, which is the runtime check of the
        same-step-snapshots-are-bitwise-identical invariant.

        ``donor_addrs``, when it names two or more live donors serving
        the SAME step, enables the TORRENT-STRIPED fetch
        (docs/design/sharded_update.md): the missing leaves are
        partitioned into contiguous byte-balanced stripes, one per
        donor, fetched CONCURRENTLY (wall-clock target ~1/N_donors);
        every leaf still digest-verifies against the one adopted
        manifest, which is what makes mixing donors sound. A donor that
        dies mid-stripe is dropped and only its REMAINING stripe is
        reassigned to the survivors on the next round
        (``bytes_resumed`` counts exactly that traffic); when the whole
        set dies the ``donors`` failover resolver above is the last
        resort. ``stripe_seed`` deterministically shuffles the donor
        order so concurrent healers spread their load instead of all
        opening their first stream against the same donor.

        ``stats``, when given, is filled with truthful counters:
        ``bytes`` (payload bytes actually read off the wire, across all
        attempts — NOT the donor's Content-Length claim),
        ``payload_bytes`` (full serialized size), ``bytes_resumed``
        (bytes fetched by resumed attempts after the first),
        ``donor_failovers``, ``digest_mismatches``, and ``attempts`` —
        filled on failure too, so a FAILED heal's wire cost and attempt
        history still reach the caller's metrics/event log — and the
        stages' busy times ``manifest_ms`` (waiting for the donor's
        digest pass), ``recv_ms`` (in the socket), ``verify_ms``
        (crc32), ``place_ms`` (``device_put`` and its copy): their sum
        over the transfer's wall says how far the stages overlapped.
        ``progress_cb(bytes_committed, payload_bytes)`` fires after every
        verified leaf. Chaos injection uses per-donor endpoints
        ``heal:<host:port>`` (channel ``heal``)."""
        logger.info("fetching checkpoint from %s", address)
        t0 = time.perf_counter()
        pol = (retry_policy if retry_policy is not None
               else RetryPolicy(max_attempts=1))
        stall = (stall_timeout_sec if stall_timeout_sec is not None
                 else timeout_sec)
        deadline = (t0 + pol.overall_deadline_ms / 1e3
                    if pol.overall_deadline_ms > 0 else None)
        # The staging buffer is the transfer's own until the placed copy
        # is ready, so placement need not copy it first.
        dput = (functools.partial(device_put_like, copy=False)
                if device_put else None)
        session = _HealSession(target, dput)
        session.tracer = tracer
        if session.widest > DEFAULT_BATCH_BYTES:
            session.staging.warm(session.widest)
        # Striped donor set: seed-shuffled so concurrent healers spread
        # their first streams; the quorum's primary rides along
        # (deduped) as one donor among equals.
        stripe: List[str] = []
        if donor_addrs:
            stripe = list(dict.fromkeys(list(donor_addrs) + [address]))
            if len(stripe) >= 2:
                import random as _random

                _random.Random(stripe_seed).shuffle(stripe)
                address = stripe[0]
            else:
                stripe = []
        try:
            out = cls._run_heal_loop(
                session, address, stall, auth_token, pol, deadline,
                donors, max_donor_failovers, progress_cb, retry_stats,
                stripe=stripe)
        finally:
            # Fill stats on BOTH outcomes: a failed heal's wire cost,
            # attempts, and failovers are exactly what the runbook's
            # "heal keeps failing" diagnosis reads from the event log.
            if stats is not None:
                stats["bytes"] = float(session.bytes_read)
                stats["payload_bytes"] = float(session.total_len)
                stats["bytes_resumed"] = float(session.bytes_resumed)
                stats["donor_failovers"] = float(session.failovers)
                stats["digest_mismatches"] = float(
                    session.digest_mismatches)
                stats["attempts"] = float(session.rounds)
                stats["donors_used"] = float(
                    max(len(session.donors_used), 1))
                stats["stripe_donor_deaths"] = float(
                    session.stripe_deaths)
                stats["redials_avoided"] = float(
                    session.pool.redials_avoided)
                for stage in HEAL_STAGES:
                    stats[f"{stage}_ms"] = session.clock.ms(stage)
            session.pool.close()
        dt = time.perf_counter() - t0
        logger.info(
            "checkpoint transfer: %.1f MB in %.2fs (%.0f MB/s; "
            "%d attempt(s), %d donor(s), %.1f MB resumed, "
            "%d failover(s), %d digest mismatch(es))",
            session.bytes_read / 1e6, dt,
            session.bytes_read / 1e6 / max(dt, 1e-9), session.rounds,
            max(len(session.donors_used), 1),
            session.bytes_resumed / 1e6, session.failovers,
            session.digest_mismatches)
        return out

    @classmethod
    def _run_heal_loop(cls, session: "_HealSession", addr: str,
                       stall: float, auth_token: Optional[str],
                       pol: RetryPolicy, deadline: Optional[float],
                       donors: Optional[Callable[[int], Optional[str]]],
                       max_donor_failovers: int,
                       progress_cb: Optional[Callable[[int, int], None]],
                       retry_stats: Optional[RetryStats],
                       stripe: Optional[List[str]] = None) -> Any:
        stripe = stripe or []
        endpoint = _heal_endpoint(addr)
        attempts = max(int(pol.max_attempts), 1)
        no_progress = 0
        legacy: Optional[bool] = None
        need_manifest = True
        while True:
            if stripe and addr not in stripe:
                # The striped wave dropped the manifest donor as dead;
                # the SAME transfer continues against the survivors.
                addr = stripe[0]
                endpoint = _heal_endpoint(addr)
            marker = len(session.committed)
            try:
                if legacy is not True and need_manifest:
                    t0 = time.perf_counter()
                    try:
                        with session.span("heal_manifest", donor=addr):
                            mf = cls._fetch_manifest(
                                addr, stall, auth_token, endpoint,
                                pool=session.pool)
                    finally:
                        session.clock.add("manifest",
                                          time.perf_counter() - t0)
                    if mf is None:
                        legacy = True
                        logger.info(
                            "heal: %s has no manifest; using legacy "
                            "non-resumable fetch", addr)
                    else:
                        legacy = False
                        session.adopt_manifest(mf)
                        need_manifest = False
                if legacy:
                    session.rounds += 1
                    return cls._legacy_fetch(
                        addr, session.target, stall, auth_token,
                        session.device_put_fn, session, endpoint)
                if not session.complete():
                    session.rounds += 1
                    if len(stripe) > 1:
                        cls._fetch_striped(session, stripe, stall,
                                           auth_token, progress_cb)
                    else:
                        for span in session.spans():
                            cls._fetch_span(addr, session, span, stall,
                                            auth_token, endpoint,
                                            progress_cb)
                if session.complete():
                    return session.assemble()
                # Remaining leaves either mismatched their digest
                # (corruption in transit — bounded per leaf by
                # MAX_LEAF_REFETCHES inside _fetch_span) or rode a
                # striped donor that died mid-wave: transient either
                # way, the next round re-spans only what's left.
                raise LeafDigestError(
                    f"{len(session.missing())} leaves still missing "
                    "after this round (digest mismatch or dropped "
                    "striped donor); re-fetching")
            except Exception as e:  # noqa: BLE001 — classified below
                transient = _heal_transient(e)
                dead = (isinstance(e, HealCorruptError)
                        or _looks_donor_dead(e))
                if not transient and not dead:
                    raise
                if len(session.committed) > marker:
                    no_progress = 0
                else:
                    no_progress += 1
                if dead and getattr(e, "_heal_striped_handled", False) \
                        and stripe:
                    # A striped wave already evicted the donor(s) that
                    # actually died — `addr` may well be a healthy
                    # survivor (the exception belongs to ANOTHER
                    # donor's thread). Re-stripe over the survivors;
                    # the loop head re-targets if addr was the victim.
                    no_progress = 0
                    continue
                if dead and addr in stripe and len(stripe) > 1:
                    # A striped peer remains: drop the dead donor and
                    # reassign its stripe instead of burning a failover
                    # (the failover resolver stays the LAST resort, for
                    # when the whole advertised set is gone).
                    stripe.remove(addr)
                    with session.lock:
                        session.stripe_deaths += 1
                    logger.warning(
                        "heal: striped donor %s dead (%s); continuing "
                        "with %d survivor(s)", addr, e, len(stripe))
                    no_progress = 0
                    continue
                if ((dead or no_progress >= attempts)
                        and donors is not None
                        and session.failovers < max_donor_failovers):
                    nxt: Optional[str] = None
                    try:
                        nxt = donors(session.failovers)
                    except Exception:  # noqa: BLE001
                        logger.exception("heal: donor resolver failed")
                    if nxt:
                        logger.warning(
                            "heal: donor %s unusable (%s); failing over "
                            "to %s with %d/%d leaves committed", addr, e,
                            nxt, len(session.committed),
                            len(session.pairs or ()))
                        session.failovers += 1
                        addr = nxt
                        endpoint = _heal_endpoint(addr)
                        # The advertised stripe set is spent — the
                        # resolver's donor is authoritative now, and a
                        # stale stripe entry must not re-capture addr at
                        # the top of the loop.
                        stripe.clear()
                        need_manifest = True
                        legacy = None
                        no_progress = 0
                        continue
                if not transient or no_progress >= attempts:
                    if retry_stats is not None and no_progress > 0:
                        retry_stats.record_giveup()
                    raise
                delay = pol.delay_ms(min(max(no_progress - 1, 0), 16)) / 1e3
                if (deadline is not None
                        and time.perf_counter() + delay > deadline):
                    if retry_stats is not None:
                        retry_stats.record_giveup()
                    raise RetryError(
                        f"heal.fetch: overall retry deadline "
                        f"({pol.overall_deadline_ms:.0f}ms) exhausted"
                    ) from e
                if retry_stats is not None:
                    retry_stats.record_retry(delay * 1e3)
                logger.warning(
                    "heal fetch attempt failed (%s); retrying from "
                    "%d/%d committed leaves", e, len(session.committed),
                    len(session.pairs or ()))
                time.sleep(delay)

    @staticmethod
    def _fetch_manifest(addr: str, stall: float,
                        auth_token: Optional[str],
                        endpoint: str,
                        pool: Optional[_ConnectionPool] = None
                        ) -> Optional[dict]:
        """GET the donor's transfer manifest; ``None`` when the donor
        cannot serve one (404: lock_streaming mode or an older build) —
        the caller then uses the legacy whole-stream fetch."""
        tok = chaos.begin(endpoint, "manifest")
        try:
            resp = _open_url(addr + MANIFEST_SUFFIX, stall, auth_token,
                             pool=pool)
        except urllib.error.HTTPError as e:
            reason = str(getattr(e, "reason", "") or e).lower()
            # 404: this build, lock_streaming mode. 400 "bad step": a
            # PRE-manifest build, whose handler parses the step out of
            # "<step>/manifest" and chokes — either way, no manifest to
            # be had; fall back to the legacy whole-stream fetch. A real
            # step mismatch says "invalid checkpoint requested" and
            # stays fatal.
            if e.code == 404 or (e.code == 400 and "bad step" in reason):
                chaos.end(tok)
                return None
            raise
        with resp:
            # Read to EOF in bounded pieces: a single read(-1) could be
            # truncated by the chaos kill clamp (or a flaky transport)
            # and then fail as a confusing JSON parse error — looping
            # lets the truncation surface as the transport error it is,
            # and a short body below is checked against Content-Length.
            reader = chaos.wrap_reader(resp, endpoint)
            want = int(resp.headers.get("Content-Length", -1))
            parts = []
            while True:
                piece = reader.read(65536)
                if not piece:
                    break
                parts.append(piece)
            body = b"".join(parts)
            if 0 <= want != len(body):
                raise ValueError("truncated checkpoint manifest")
        chaos.end(tok)
        mf = json.loads(body)
        if mf.get("format") != MANIFEST_FORMAT:
            raise ValueError(
                f"invalid checkpoint manifest format {mf.get('format')!r}")
        return mf

    @classmethod
    def _fetch_span(cls, addr: str, session: "_HealSession", span: list,
                    stall: float, auth_token: Optional[str],
                    endpoint: str,
                    progress_cb: Optional[Callable[[int, int], None]]
                    ) -> None:
        """Fetch one contiguous byte span of missing leaves via an HTTP
        Range request; verify + commit each leaf as it lands. Raises on
        transport failure (committed leaves are retained by the session)
        and :class:`HealCorruptError` when a leaf keeps mismatching.
        Requests ride the session's persistent per-donor connection
        pool, so a multi-span wave pays one TCP dial per donor, not one
        per span. Each span fetch records a ``heal_stripe`` trace span
        tagged with its donor (a failing fetch's span carries the error
        — the timeline's attribution of WHICH donor stalled/corrupted
        a heal)."""
        a, b, idxs = span
        with session.span("heal_stripe", donor=addr, leaves=len(idxs),
                          bytes=b - a):
            cls._fetch_span_body(addr, session, span, stall, auth_token,
                                 endpoint, progress_cb)

    @staticmethod
    def _fetch_span_body(addr: str, session: "_HealSession", span: list,
                         stall: float, auth_token: Optional[str],
                         endpoint: str,
                         progress_cb: Optional[Callable[[int, int], None]]
                         ) -> None:
        a, b, idxs = span
        tok = chaos.begin(endpoint, "fetch")
        resp = _open_url(addr, stall, auth_token,
                         headers={"Range": f"bytes={a}-{b - 1}"},
                         pool=session.pool)
        counter = [0]
        recv_s = 0.0
        pipe = _LeafPipeline(session, addr, progress_cb)
        try:
            reader = _CountingReader(
                chaos.wrap_reader(resp, endpoint), counter)
            status = getattr(resp, "status", None) or resp.getcode()
            if status == 200 and a > 0:
                # Server ignored Range (shouldn't happen against our own
                # CheckpointServer): discard the prefix. The discarded
                # bytes are still counted — they really crossed the wire.
                remaining = a
                while remaining > 0:
                    chunk = reader.read(min(1 << 20, remaining))
                    if not chunk:
                        raise ValueError("truncated checkpoint stream")
                    remaining -= len(chunk)
            with session.span("heal_recv", donor=addr) as sp:
                for i in idxs:
                    buf = session.staging.take(
                        int(session.pairs[i][0]["nbytes"]))
                    mv = memoryview(buf)
                    off = 0
                    try:
                        while off < len(buf):
                            if pipe.error is not None:
                                raise pipe.error
                            n = min(DEFAULT_CHUNK_BYTES, len(buf) - off)
                            t0 = time.perf_counter()
                            _read_exact_into(reader, mv[off:off + n])
                            recv_s += time.perf_counter() - t0
                            pipe.landed(i, buf, off, n)
                            off += n
                    finally:
                        if not off:     # never handed to the pipeline
                            session.staging.give(buf, True)
                sp.set(busy_ms=round(recv_s * 1e3, 3), leaves=len(idxs))
        finally:
            pipe.close()
            session.clock.add("recv", recv_s)
            resp.close()
            session.note_bytes(counter[0])
        if pipe.error is not None:
            raise pipe.error
        chaos.end(tok)

    @classmethod
    def _fetch_striped(cls, session: "_HealSession", stripe: List[str],
                       stall: float, auth_token: Optional[str],
                       progress_cb: Optional[Callable[[int, int], None]]
                       ) -> None:
        """One torrent-striped wave: partition the missing leaves into
        contiguous byte-balanced stripes, one per live donor, and fetch
        them CONCURRENTLY (one thread per donor; each stripe collapses
        to a handful of coalesced Range requests). Every leaf verifies
        against the one adopted manifest regardless of which donor
        served it — the same-step bitwise-identity invariant, checked
        per leaf.

        Donors whose thread fails DEAD (refused dial, persistently
        corrupt copy) are removed from ``stripe`` in place, so the next
        wave re-partitions only the remaining bytes over the survivors.
        Raises only when NO leaf landed this wave (all donors failed) —
        a partial wave returns so the caller's progress accounting
        resets the retry budget and re-stripes the remainder."""
        groups = session.stripes(len(stripe))
        before = len(session.committed)
        failures: List[Tuple[str, BaseException]] = []
        flock = threading.Lock()

        def fetch(donor: str, idxs: List[int]) -> None:
            try:
                for span in session.spans_for(idxs):
                    cls._fetch_span(donor, session, span, stall,
                                    auth_token, _heal_endpoint(donor),
                                    progress_cb)
            except BaseException as e:  # noqa: BLE001 — classified below
                with flock:
                    failures.append((donor, e))

        threads = [
            threading.Thread(target=fetch, args=(donor, idxs),
                             name=f"heal-stripe-{k}", daemon=True)
            for k, (donor, idxs) in enumerate(zip(stripe, groups))
            if idxs
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        primary_exc: Optional[BaseException] = None
        for donor, e in failures:
            if (isinstance(e, HealCorruptError) or _looks_donor_dead(e)) \
                    and donor in stripe and len(stripe) > 1:
                stripe.remove(donor)
                with session.lock:
                    session.stripe_deaths += 1
                logger.warning(
                    "heal: striped donor %s died mid-stripe (%s); its "
                    "remaining leaves reassign to %d survivor(s)",
                    donor, e, len(stripe))
            if primary_exc is None or donor == stripe[0]:
                primary_exc = e
        if failures and len(session.committed) == before:
            # Dead donors were already evicted above — flag that so the
            # caller's own eviction branch doesn't blame the CURRENT
            # manifest donor for a different donor's death.
            primary_exc._heal_striped_handled = True  # noqa: SLF001
            raise primary_exc  # zero-progress wave: let the caller classify

    @staticmethod
    def _legacy_fetch(addr: str, target: T, stall: float,
                      auth_token: Optional[str],
                      device_put_fn: Optional[Callable],
                      session: "_HealSession", endpoint: str) -> T:
        """Whole-stream fetch for donors without a manifest. Restarts
        from byte 0 on every attempt; bytes are still counted truthfully
        via the wrapping reader (never the Content-Length claim)."""
        tok = chaos.begin(endpoint, "fetch")
        resp = _open_url(addr, stall, auth_token, pool=session.pool)
        counter = [0]
        try:
            # Best-effort payload size for the progress gauge /
            # resume-ratio consumers; the Content-Length CLAIM is fine
            # here because stats["bytes"] stays counted, not claimed.
            claimed = int(resp.headers.get("Content-Length", 0) or 0)
            if claimed > 0 and session.total_len == 0:
                session.total_len = claimed
            out = load_pytree_from(
                _CountingReader(chaos.wrap_reader(resp, endpoint),
                                counter),
                target, device_put_fn=device_put_fn)
        finally:
            resp.close()
            session.note_bytes(counter[0])
        chaos.end(tok)
        return out
