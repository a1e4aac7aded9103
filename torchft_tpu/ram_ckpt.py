"""RAM checkpoint tier: peer-replicated in-memory snapshots with tiered
async demotion (docs/design/memory_tier.md).

The common failure at fleet scale is UNCORRELATED — one group dies while
its peers keep bitwise-identical state in host RAM. Durable saves are
disk-first, so a replacement's catch-up was disk-bandwidth-bound even
though the same bytes sit one NIC hop away. This module makes peer RAM
the first rung of the recovery ladder:

* :func:`encode_image` serializes one committed ``{user, torchft}``
  snapshot into a single in-memory **v2 image** — byte-identical to the
  on-disk ``TFTCKPT2`` format (:func:`torchft_tpu.checkpoint_io.
  _write_v2_stream` is the shared writer), digests computed in the same
  single write pass the trailing manifest exists for. One encode feeds
  every rung: RAM, peers, local disk, durable store are all plain byte
  copies of the same verified image.
* :class:`RamCheckpointStore` holds verified images step-keyed and
  bounded, accepts peer pushes as staged ranged writes that are
  **crc-verified before acceptance** (the full digest scan of
  :func:`~torchft_tpu.checkpoint_io._verify_stream` — a torn or
  corrupted push can never become servable), and serves the image's
  payload region to healers. Because the v2 payload region IS the
  serialized ``{user, torchft}`` pytree stream, the existing striped,
  resumable, digest-verified healer
  (:meth:`~torchft_tpu.checkpointing.CheckpointServer.load_from_address`)
  works against ``…/ramckpt/{step}`` unchanged — the bitwise
  convergence oracle comes for free.
* :class:`RamReplicator` runs the commit-coupled pipeline off the
  training loop on the :class:`~torchft_tpu.checkpoint_io.
  AsyncCheckpointer` machinery's discipline — one job in flight, a
  no-progress stall watchdog
  (:class:`~torchft_tpu.checkpoint_io.CheckpointStallError`), transient
  IO retried (:func:`~torchft_tpu.checkpoint_io._io_transient`), the
  fatal ENOSPC/EROFS class surfaced sticky
  (:func:`~torchft_tpu.checkpoint_io._io_fatal`): push the image to K
  peer hosts over ranged HTTP PUTs, then demote RAM → local disk →
  durable store asynchronously, each stage timed into
  ``demote_stage_ms_total``.

Chaos (docs/design/chaos_and_retry.md): every push, accept, and serve
passes through :func:`torchft_tpu.chaos.ram_fault` on the ``ram``
channel — peer-RAM loss (``ram_loss_rate``), replication blackhole
(``ram_blackhole_rate``), and correlated K-peer death (the
``kill_endpoint`` latches) drive the failure-mode battery, so the
ladder degrades rung by rung instead of falling off a cliff.
"""

from __future__ import annotations

import http.client
import io
import json
import logging
import os
import threading
import time
import urllib.parse
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Any, Callable, Dict, List, Optional, Tuple

from torchft_tpu import chaos, transport
from torchft_tpu.boundary import Boundary, BoundaryFeature
from torchft_tpu.checkpoint_io import (
    CheckpointCorruptError,
    CheckpointStallError,
    _atomic_publish,
    _build_head,
    _flip_byte,
    _io_fatal,
    _io_transient,
    _load_v2_stream,
    _open_verified,
    _verify_stream,
    _write_v2_stream,
)
from torchft_tpu.retry import RetryPolicy, RetryStats, call_with_retry
from torchft_tpu.serialization import plan_pytree

logger: logging.Logger = logging.getLogger(__name__)

# The transfer-manifest spelling healers validate
# (torchft_tpu.checkpointing.MANIFEST_FORMAT — duplicated here to keep
# this module importable without the HTTP server module).
TRANSFER_MANIFEST_FORMAT = "tft-manifest-1"

# Push chunk size for peer replication PUTs: big enough to amortize
# header overhead, small enough that the stall watchdog's progress
# clock ticks on a sane cadence through a capped NIC.
_PUSH_CHUNK = 8 << 20

_RAM_STAGES = ("encode", "ram", "replicate", "disk", "durable")


class RamImage:
    """One verified in-memory checkpoint image: the full v2 byte stream
    plus its parsed geometry. Immutable once constructed; the payload
    region (the serialized ``{user, torchft}`` pytree) is exposed as a
    zero-copy memoryview for ranged serving."""

    __slots__ = ("data", "head", "manifest", "payload_start",
                 "payload_len")

    def __init__(self, data: bytes, head: dict, manifest: dict,
                 payload_start: int, payload_len: int) -> None:
        self.data = data
        self.head = head
        self.manifest = manifest
        self.payload_start = payload_start
        self.payload_len = payload_len

    @property
    def step(self) -> int:
        return int(self.head.get("step", 0))

    @property
    def nbytes(self) -> int:
        return len(self.data)

    def payload_view(self) -> memoryview:
        """The serialized pytree stream — exactly what a healer's ranged
        GETs address (offset 0 = stream start, like the live heal
        endpoint)."""
        return memoryview(self.data)[
            self.payload_start:self.payload_start + self.payload_len]

    def transfer_manifest(self) -> dict:
        """The heal-protocol manifest for this image: the durable
        trailer's digest/geometry core under the transfer format tag the
        healer validates. The trailer's extra ``head_crc32``/
        ``preamble_crc32`` keys ride along harmlessly."""
        return {"format": TRANSFER_MANIFEST_FORMAT, "step": self.step,
                **self.manifest}


def _parse_image(data: bytes) -> RamImage:
    """Structural parse (head + trailer geometry + head digest) of a v2
    byte string — no payload digest scan; see :func:`verify_image`."""
    f = io.BytesIO(data)
    head, mf, payload_start = _open_verified(f)
    return RamImage(data, head, mf, payload_start,
                    int(head["payload_len"]))


def encode_image(user_state: Any, manager_state: Optional[dict] = None,
                 meta: Optional[dict] = None,
                 _progress: Optional[Callable[[int], None]] = None
                 ) -> RamImage:
    """Serialize one ``{user, torchft}`` snapshot into a v2 image —
    byte-identical to what :func:`torchft_tpu.checkpoint_io.save` puts
    on disk, so every later rung (peer push, disk demotion, durable
    copy) is a plain byte copy of already-digested bytes. The caller
    owns snapshot safety (pass donation-immune state — the Manager
    passes the checkpoint server's commit snapshot)."""
    tree = {
        "user": user_state,
        "torchft": manager_state or {"step": 0, "batches_committed": 0},
    }
    plan = plan_pytree(tree)
    head_bytes = json.dumps(
        _build_head(plan, manager_state, meta)).encode()
    buf = io.BytesIO()
    _write_v2_stream(buf, plan, head_bytes, _progress)
    return _parse_image(buf.getvalue())


def verify_image(data: bytes) -> RamImage:
    """Full digest verification of an image byte string (head, preamble,
    every array leaf's crc32 — the same scan as
    :func:`torchft_tpu.checkpoint_io.verify`); returns the parsed
    :class:`RamImage` on success, raises
    :class:`~torchft_tpu.checkpoint_io.CheckpointCorruptError`
    otherwise. This is the acceptance gate for peer-pushed bytes: an
    image is stored iff it is provably the donor's bitwise state."""
    _verify_stream(io.BytesIO(data))
    return _parse_image(data)


def load_image(data: bytes, target: Any, device_put: bool = True
               ) -> Tuple[Any, dict]:
    """Load an image back into ``target``'s structure (and shardings
    when ``device_put``) with the disk path's digest-verified load
    discipline. Returns ``(user_state, manager_state)``."""
    from torchft_tpu.serialization import device_put_like

    wrapped = {"user": target,
               "torchft": {"step": 0, "batches_committed": 0}}
    dput = device_put_like if device_put else None
    tree = _load_v2_stream(io.BytesIO(data), wrapped, dput,
                           what="ram image")
    return tree["user"], tree["torchft"]


class _Stage:
    """One in-progress peer push: a preallocated buffer plus merged
    coverage intervals, so out-of-order or re-sent ranges (a retried
    chunk after a reset) land idempotently."""

    __slots__ = ("buf", "ivs", "origin", "t0")

    def __init__(self, total: int, origin: str) -> None:
        self.buf = bytearray(total)
        self.ivs: List[List[int]] = []   # merged, sorted [start, end)
        self.origin = origin
        self.t0 = time.monotonic()

    def write(self, offset: int, data: bytes) -> None:
        end = offset + len(data)
        if end > len(self.buf):
            raise ValueError(
                f"range [{offset}, {end}) exceeds staged image size "
                f"{len(self.buf)}")
        self.buf[offset:end] = data
        self.ivs.append([offset, end])
        self.ivs.sort()
        merged = [self.ivs[0]]
        for a, b in self.ivs[1:]:
            if a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        self.ivs = merged

    def complete(self) -> bool:
        return self.ivs == [[0, len(self.buf)]]


class RamCheckpointStore:
    """Step-keyed store of verified checkpoint images in host RAM.

    Three producers feed it: the local replicator (its own commit
    image), peer pushes (staged ranged writes, verified before
    acceptance), and nothing else — there is no unverified path in.
    One consumer drains it: healers, served the payload region over the
    owning :class:`~torchft_tpu.checkpointing.CheckpointServer`'s
    ``/ramckpt/*`` routes.

    Bounded two ways: ``keep`` newest steps (replica groups advance in
    lockstep, so deep history is dead weight) and ``max_bytes`` total
    (env ``TORCHFT_RAM_CKPT_BYTES``; the oldest images evict first).
    ``chaos_scope`` (``ram:<name>``) arms the fault hook: a ``ram_loss``
    decision on a serve silently drops the stored image — the
    peer-RAM-loss band healers must survive by falling down a rung."""

    def __init__(self, keep: int = 2, max_bytes: Optional[int] = None,
                 chaos_scope: Optional[str] = None) -> None:
        if max_bytes is None:
            max_bytes = int(
                os.environ.get("TORCHFT_RAM_CKPT_BYTES", 2 << 30))
        self._keep = max(int(keep), 1)
        self._max_bytes = int(max_bytes)
        self._chaos_scope = chaos_scope
        self._lock = threading.Lock()
        self._images: Dict[int, RamImage] = {}
        self._staging: Dict[int, _Stage] = {}
        self._m: Dict[str, float] = {
            "ram_ckpt_images": 0.0,
            "ram_ckpt_stored_bytes": 0.0,
            "ram_ckpt_accepts_total": 0.0,
            "ram_ckpt_rejects_total": 0.0,
            "ram_ckpt_evictions_total": 0.0,
            "ram_ckpt_losses_total": 0.0,
        }

    # ------------------------------------------------------------ write

    def put(self, image: RamImage, origin: str = "local") -> bool:
        """Insert an already-verified image; returns False when the step
        is already held (peers replicate bitwise-identical state, so a
        duplicate push carries no new information)."""
        with self._lock:
            if image.step in self._images:
                return False
            self._images[image.step] = image
            self._staging.pop(image.step, None)
            self._m["ram_ckpt_accepts_total"] += 1
            self._evict_locked()
            self._refresh_gauges_locked()
        logger.debug("ram store: accepted step %d (%d B) from %s",
                     image.step, image.nbytes, origin)
        return True

    def put_bytes(self, data: bytes, origin: str = "peer") -> RamImage:
        """Verify-then-store a complete image byte string (single-shot
        push); raises ``CheckpointCorruptError`` on any digest failure
        — rejected bytes are never stored."""
        try:
            image = verify_image(bytes(data))
        except CheckpointCorruptError:
            with self._lock:
                self._m["ram_ckpt_rejects_total"] += 1
            raise
        self.put(image, origin=origin)
        return image

    def stage_write(self, step: int, offset: int, data: bytes,
                    total: int, origin: str = "peer"
                    ) -> Optional[RamImage]:
        """Accept one ranged chunk of a peer push. When the last byte
        lands the assembled image is digest-verified and (only then)
        stored — returns the accepted image, or None while incomplete.
        A failed verification drops the whole staging buffer and raises
        ``CheckpointCorruptError`` (the pusher sees 422 and may retry
        from scratch)."""
        if self._chaos_scope is not None:
            chaos.ram_fault(self._chaos_scope, op="accept")
        with self._lock:
            if step in self._images:
                return self._images[step]  # idempotent re-push
            st = self._staging.get(step)
            if st is None or len(st.buf) != total:
                st = self._staging[step] = _Stage(total, origin)
            st.write(offset, data)
            done = st.complete()
            if done:
                del self._staging[step]
                buf = bytes(st.buf)
        if not done:
            return None
        return self.put_bytes(buf, origin=origin)

    # ------------------------------------------------------------- read

    def get(self, step: int) -> Optional[RamImage]:
        """The stored image for ``step``, or None. Serve-path chaos
        applies here: a ``ram_loss`` decision drops the image first (it
        was silently reclaimed), so the caller observes a 404 and falls
        down the recovery ladder."""
        if self._chaos_scope is not None:
            try:
                d = chaos.ram_fault(self._chaos_scope, op="serve")
            except (ConnectionError, OSError):
                # A dead/reset RAM host serves nothing; the healer's
                # transport error handling (donor failover) owns this.
                return None
            if d is not None and d.fault == "ram_loss":
                with self._lock:
                    if self._images.pop(step, None) is not None:
                        self._m["ram_ckpt_losses_total"] += 1
                        self._refresh_gauges_locked()
                logger.warning(
                    "ram store: [chaos] step %d image lost", step)
                return None
        with self._lock:
            return self._images.get(step)

    def latest(self) -> Optional[RamImage]:
        with self._lock:
            if not self._images:
                return None
            step = max(self._images)
        return self.get(step)

    def steps(self) -> List[int]:
        with self._lock:
            return sorted(self._images)

    def drop(self, step: int) -> None:
        with self._lock:
            self._images.pop(step, None)
            self._staging.pop(step, None)
            self._refresh_gauges_locked()

    def clear(self) -> None:
        with self._lock:
            self._images.clear()
            self._staging.clear()
            self._refresh_gauges_locked()

    # ------------------------------------------------------- accounting

    def _evict_locked(self) -> None:
        steps = sorted(self._images)
        while len(steps) > self._keep or (
                len(steps) > 1
                and sum(im.nbytes for im in self._images.values())
                > self._max_bytes):
            self._images.pop(steps.pop(0), None)
            self._m["ram_ckpt_evictions_total"] += 1

    def _refresh_gauges_locked(self) -> None:
        self._m["ram_ckpt_images"] = float(len(self._images))
        self._m["ram_ckpt_stored_bytes"] = float(
            sum(im.nbytes for im in self._images.values()))

    def metrics(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._m)


def push_image(base_url: str, image: RamImage,
               auth_token: Optional[str] = None,
               timeout_sec: float = 30.0,
               chunk_bytes: int = _PUSH_CHUNK,
               progress: Optional[Callable[[int], None]] = None,
               chaos_scope: Optional[str] = None) -> int:
    """Push one image to a peer's ``/ramckpt/{step}`` endpoint as
    sequential ranged PUTs over one kept-alive connection — the
    torrent-heal byte path run in reverse (push-side ranged writes
    against the same digest-manifested stream). The peer verifies the
    assembled image before acceptance; a 422 means OUR bytes failed ITS
    digest scan, which violates the bitwise invariant — surfaced as
    ``CheckpointCorruptError``, never retried silently. Returns bytes
    pushed."""
    u = urllib.parse.urlparse(base_url)
    netloc = u.netloc
    path = u.path.rstrip("/") + f"/ramckpt/{image.step}"
    scope = chaos_scope or f"ram:{netloc}"
    try:
        return transport.push_ranged(
            base_url, path, memoryview(image.data),
            auth_token=auth_token, timeout_sec=timeout_sec,
            chunk_bytes=chunk_bytes, qos=transport.QoS.DEMOTION,
            fault=lambda: chaos.ram_fault(scope, op="push"),
            progress=progress)
    except transport.PushRejectedError as e:
        raise CheckpointCorruptError(
            f"peer {netloc} rejected step {image.step} image: "
            f"{e.body[:200]!r}") from None


def peer_steps(base_url: str, auth_token: Optional[str] = None,
               timeout_sec: float = 5.0) -> List[int]:
    """Steps a peer's RAM tier currently holds
    (``GET {base}/ramckpt/steps``), ascending. Empty on ANY failure —
    probing is best-effort rung selection, never a correctness gate
    (the disk rung covers a wrong answer)."""
    try:
        doc = transport.fetch_json(
            f"{base_url.rstrip('/')}/ramckpt/steps",
            stall=timeout_sec, auth_token=auth_token)
        return sorted(int(s) for s in doc.get("steps", []))
    except Exception:  # noqa: BLE001 — probe failure = empty rung
        return []


class _ReplicateJob:
    """One background replication+demotion run: its Future, progress
    clock, and the abandoned latch the stall watchdog uses to disown
    it (mirrors :class:`torchft_tpu.checkpoint_io._SaveJob`)."""

    __slots__ = ("step", "future", "bytes_done", "last_progress",
                 "abandoned")

    def __init__(self, step: int) -> None:
        self.step = step
        self.future: Future = Future()
        self.bytes_done = 0
        self.last_progress = time.monotonic()
        self.abandoned = False

    def note(self, nbytes: int) -> None:
        self.bytes_done += nbytes
        self.last_progress = time.monotonic()


class RamReplicator:
    """Commit-coupled replication + tiered demotion, off the training
    loop. One job in flight (a newer commit must never be overtaken by
    an older one racing the same peers/files); stage order per job:

    1. ``ram``       — the image enters the local
       :class:`RamCheckpointStore` (peers heal from it immediately).
    2. ``replicate`` — ranged-PUT pushes to up to ``k`` peers from
       ``peers_fn()`` (the Manager's healset-derived discovery —
       no parallel donor registry). Per-peer failures are counted and
       skipped; the job only fails when EVERY candidate refuses.
    3. ``disk``      — the image bytes land at
       ``{demote_dir}/{prefix}{step}`` via the atomic-publish sequence
       (findable by :func:`torchft_tpu.checkpoint_io.recover` —
       the local-disk rung of cold start).
    4. ``durable``   — the same bytes copy to ``durable_dir`` (the
       correlated-failure rung).

    Single-write-pass digests: the image was digested when encoded;
    every rung is a byte copy, and each rung's readers re-verify
    against the embedded manifest. Stage walls accumulate into
    ``demote_stage_ms_total`` (and per-stage ``demote_<stage>_ms``);
    transient IO retries under ``retry_policy``
    (:func:`~torchft_tpu.checkpoint_io._io_transient`); the fatal
    ENOSPC/EROFS class counts ``ram_demote_fatal`` and latches
    ``last_error`` sticky; a job with no progress for
    ``stall_timeout_sec`` is abandoned with
    :class:`~torchft_tpu.checkpoint_io.CheckpointStallError` exactly
    like the durable writer."""

    def __init__(self, store: RamCheckpointStore,
                 peers_fn: Callable[[], List[str]],
                 k: int = 2,
                 demote_dir: Optional[str] = None,
                 durable_dir: Optional[str] = None,
                 prefix: str = "ckpt_",
                 auth_token: Optional[str] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 retry_stats: Optional[RetryStats] = None,
                 stall_timeout_sec: Optional[float] = None,
                 push_timeout_sec: float = 30.0,
                 chaos_scope: Optional[str] = None) -> None:
        if stall_timeout_sec is None:
            stall_timeout_sec = float(
                os.environ.get("TORCHFT_RAM_STALL_SEC")
                or os.environ.get("TORCHFT_CKPT_STALL_SEC", 60.0))
        self._store = store
        self._peers_fn = peers_fn
        self._k = max(int(k), 0)
        self._demote_dir = demote_dir
        self._durable_dir = durable_dir
        self._prefix = prefix
        self._auth_token = auth_token
        self._retry_policy = retry_policy
        self._retry_stats = retry_stats
        self._stall_sec = float(stall_timeout_sec)
        self._push_timeout = float(push_timeout_sec)
        self._chaos_scope = chaos_scope
        self._job: Optional[_ReplicateJob] = None
        self._error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self._last_error: Optional[str] = None
        self._m: Dict[str, float] = {
            "ram_ckpt_replications_total": 0.0,
            "ram_ckpt_bytes_replicated_total": 0.0,
            "ram_ckpt_push_failures_total": 0.0,
            "ram_ckpt_peers": 0.0,
            "ram_demote_errors": 0.0,
            "ram_demote_fatal": 0.0,
            "ram_demote_stalls": 0.0,
            "demote_stage_ms_total": 0.0,
        }
        for stage in _RAM_STAGES:
            self._m[f"demote_{stage}_ms"] = 0.0

    # ----------------------------------------------------------- public

    def replicate_async(self, user_state: Any,
                        manager_state: Optional[dict] = None,
                        meta: Optional[dict] = None) -> Future:
        """Snapshot now, encode + replicate + demote in the background;
        returns a Future resolving to the count of peers that accepted
        the image. The snapshot is the same donation-immune on-device
        copy the durable writer takes
        (:func:`torchft_tpu.checkpointing._snapshot_tree` — HBM-speed),
        so the training loop pays milliseconds while the D2H serialize
        runs behind it. Serializes with (and surfaces the error of) the
        previous job first."""
        from torchft_tpu.checkpointing import _snapshot_tree

        self.wait()
        snap = _snapshot_tree(user_state)
        mgr = dict(manager_state) if manager_state else None
        meta = dict(meta) if meta else None
        job = _ReplicateJob(int((mgr or {}).get("step", 0)))
        t = threading.Thread(target=self._run_encode,
                             args=(job, snap, mgr, meta),
                             daemon=True, name="ram_replicator")
        self._job = job
        t.start()
        return job.future

    def replicate_image_async(self, image: RamImage) -> Future:
        """Start the pipeline for an already-encoded image (benches and
        tests; the training path uses :meth:`replicate_async`)."""
        self.wait()
        job = _ReplicateJob(image.step)
        t = threading.Thread(target=self._run, args=(job, image),
                             daemon=True, name="ram_replicator")
        self._job = job
        t.start()
        return job.future

    def wait(self) -> None:
        """Block until the in-flight job finishes — or the stall
        watchdog abandons it; re-raises a latched error."""
        job, self._job = self._job, None
        if job is not None:
            while True:
                try:
                    job.future.result(timeout=0.05)
                    break
                except FutureTimeout:
                    if (time.monotonic() - job.last_progress
                            > self._stall_sec):
                        job.abandoned = True
                        e = CheckpointStallError(
                            f"RAM replication of step {job.step} made "
                            f"no progress for {self._stall_sec:.0f}s; "
                            "abandoning the worker")
                        with self._lock:
                            self._m["ram_demote_stalls"] += 1
                            self._last_error = (
                                f"CheckpointStallError: {e}")
                            if self._error is None:
                                self._error = e
                        break
                except Exception:
                    # Latched by the worker; re-raised below.
                    break
        self._raise_pending_error()

    def shutdown(self) -> None:
        """Drain (or abandon, if stalled) the in-flight job; daemon
        worker threads never block process exit."""
        try:
            self.wait()
        except Exception:
            logger.exception("ram replicator shutdown: last job failed")

    def metrics(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._m)

    def last_error(self) -> Optional[str]:
        with self._lock:
            return self._last_error

    def _raise_pending_error(self) -> None:
        with self._lock:
            e, self._error = self._error, None
        if e is not None:
            raise RuntimeError(
                "previous RAM replication failed") from e

    # ----------------------------------------------------------- worker

    def _stage(self, job: "_ReplicateJob", name: str,
               fn: Callable[[], Any]) -> Any:
        t0 = time.perf_counter()
        try:
            return fn()
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            with self._lock:
                self._m[f"demote_{name}_ms"] += ms
                self._m["demote_stage_ms_total"] += ms
            job.note(0)

    def _run_encode(self, job: "_ReplicateJob", snap: Any,
                    mgr: Optional[dict], meta: Optional[dict]) -> None:
        try:
            image = self._stage(
                job, "encode",
                lambda: encode_image(snap, mgr, meta,
                                     _progress=lambda n: job.note(0)))
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            with self._lock:
                self._m["ram_demote_errors"] += 1
                if _io_fatal(e):
                    self._m["ram_demote_fatal"] += 1
                self._last_error = f"{type(e).__name__}: {e}"
                if not job.abandoned and self._error is None:
                    self._error = e
            try:
                job.future.set_exception(e)
            except BaseException:  # future abandoned mid-stall
                pass
            return
        self._run(job, image)

    def _run(self, job: "_ReplicateJob", image: RamImage) -> None:
        try:
            self._stage(job, "ram",
                        lambda: self._store.put(image, origin="local"))
            accepted = self._stage(
                job, "replicate", lambda: self._push_peers(job, image))
            if self._demote_dir is not None:
                self._stage(
                    job, "disk",
                    lambda: self._demote_file(job, self._demote_dir,
                                              image))
            if self._durable_dir is not None:
                self._stage(
                    job, "durable",
                    lambda: self._demote_file(job, self._durable_dir,
                                              image))
            with self._lock:
                self._m["ram_ckpt_replications_total"] += 1
            job.future.set_result(accepted)
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            with self._lock:
                self._m["ram_demote_errors"] += 1
                if _io_fatal(e):
                    self._m["ram_demote_fatal"] += 1
                self._last_error = f"{type(e).__name__}: {e}"
                # An abandoned (stalled) job must not latch: its owner
                # already recorded the stall and moved on.
                if not job.abandoned and self._error is None:
                    self._error = e
            try:
                job.future.set_exception(e)
            except BaseException:  # future abandoned mid-stall
                pass

    def _push_peers(self, job: "_ReplicateJob", image: RamImage) -> int:
        """Push to candidate peers until ``k`` accept or the list runs
        out. Per-peer transport failures skip to the next candidate (a
        down peer must not starve the rest); a digest rejection (422)
        is a bitwise-invariant violation and fails the job loudly."""
        if self._k == 0:
            with self._lock:
                self._m["ram_ckpt_peers"] = 0.0
            return 0
        peers = list(self._peers_fn() or [])
        accepted = 0
        for base in peers:
            if accepted >= self._k:
                break
            try:
                pushed = push_image(
                    base, image, auth_token=self._auth_token,
                    timeout_sec=self._push_timeout,
                    progress=job.note,
                    chaos_scope=self._chaos_scope)
            except CheckpointCorruptError:
                raise
            except (OSError, ConnectionError, http.client.HTTPException,
                    TimeoutError) as e:
                with self._lock:
                    self._m["ram_ckpt_push_failures_total"] += 1
                logger.warning("ram replicate: peer %s refused step %d "
                               "(%s); trying next", base, image.step, e)
                continue
            accepted += 1
            with self._lock:
                self._m["ram_ckpt_bytes_replicated_total"] += pushed
        with self._lock:
            self._m["ram_ckpt_peers"] = float(accepted)
        if peers and accepted == 0:
            logger.warning(
                "ram replicate: step %d reached 0 of %d candidate "
                "peers — RAM replication set is EMPTY (disk is the "
                "only rung)", image.step, len(peers))
        return accepted

    def _demote_file(self, job: "_ReplicateJob", directory: str,
                     image: RamImage) -> str:
        """One rung of demotion: the image bytes land at
        ``{directory}/{prefix}{step}`` through the crash-durable
        atomic-publish sequence — the same file family the durable
        writer uses, so :func:`~torchft_tpu.checkpoint_io.recover`
        picks demoted images up with no new scan logic."""
        path = os.path.join(directory, f"{self._prefix}{image.step}")
        os.makedirs(directory, exist_ok=True)

        def op() -> None:
            fault = chaos.disk_fault(
                f"disk:{os.path.basename(path)}", op="demote")
            if fault is not None and fault.fault == "torn":
                # Crash-before-durable-rename: a frac-prefix sits at the
                # DESTINATION path (same semantics as the durable
                # writer's torn band — recover() must quarantine it).
                with open(path, "wb") as f:
                    f.write(image.data[:int(len(image.data)
                                            * fault.frac)])
                raise OSError(
                    f"[chaos] disk:{os.path.basename(path)}: torn "
                    "demotion (crashed before rename was durable)")

            def body(f) -> None:
                view = memoryview(image.data)
                for start, end in transport.chunk_spans(
                        len(view), _PUSH_CHUNK):
                    f.write(view[start:end])
                    job.note(end - start)

            _atomic_publish(path, body)
            if fault is not None and fault.fault == "flip":
                _flip_byte(path, fault.frac)

        if self._retry_policy is not None:
            call_with_retry(op, self._retry_policy,
                            classify=_io_transient,
                            stats=self._retry_stats, op="ram.demote")
        else:
            op()
        return path


class RamTier(BoundaryFeature):
    """The tier as a commit-boundary feature of one Manager
    (docs/design/memory_tier.md): at every commit boundary the
    committed snapshot goes through one :class:`RamReplicator` job.
    Replication lands at the step edge — the same post-apply edge the
    preemption drain lands on, and for the same reason: the caller has
    applied the committed update, so the image carries step N's
    metadata over step N's params; the cost on the loop is one
    on-device snapshot. 0 peers (the default; env
    ``TORCHFT_RAM_CKPT_PEERS``) leaves the tier off.

    Built from the boundary, the checkpoint server that serves
    ``/ramckpt/*``, ``peers_fn`` (the Manager's healset-derived
    discovery), and ``snapshot`` -> ``(user_state, manager_state,
    meta)`` of the committed step."""

    # Boundary replications refused because the state was not a settled
    # committed step's (the ckpt_save_skipped analogue), dispatches that
    # raised, and replication-set collapses. The store's and the
    # replicator's own counters merge in via metrics() while the tier
    # is enabled.
    METRICS = {
        "ram_replicate_skipped": 0.0,
        "ram_replicate_errors_total": 0.0,
        "ram_replica_collapses_total": 0.0,
    }

    def __init__(self, boundary: Boundary, ckpt_server: Any,
                 peers_fn: Callable[[], List[str]],
                 snapshot: Callable[[], tuple],
                 peers: Optional[int] = None,
                 demote_dir: Optional[str] = None,
                 **replicator_kwargs: Any) -> None:
        self._b = boundary
        self._ckpt_server = ckpt_server
        self._peers_fn = peers_fn
        self._snapshot = snapshot
        self._replicator_kwargs = replicator_kwargs
        self.store: Optional[RamCheckpointStore] = None
        self.replicator: Optional[RamReplicator] = None
        self._demote_dir = (demote_dir
                            or os.environ.get("TORCHFT_RAM_DEMOTE_DIR")
                            or None)
        # High-water mark of peers that accepted a replication — a drop
        # to 0 afterwards is a replication-set collapse (flight dump).
        self._peers_seen = 0.0
        self._collapse_dumped = False
        if peers is None:
            try:
                peers = int(os.environ.get("TORCHFT_RAM_CKPT_PEERS", "0"))
            except ValueError:
                peers = 0
        self._peers_pending = max(int(peers), 0)

    def enable_pending(self) -> None:
        """Arm what the constructor asked for, once a replica id exists."""
        if self._peers_pending > 0:
            self.enable(peers=self._peers_pending)

    def enable(self, peers: int = 2, demote_dir: Optional[str] = None,
               durable_dir: Optional[str] = None, prefix: str = "ckpt_",
               keep: int = 2,
               store: Optional[RamCheckpointStore] = None) -> None:
        """Arm the tier: attach a :class:`RamCheckpointStore` to the
        checkpoint server (``/ramckpt/*`` starts serving and accepting
        peer pushes) and start commit-coupled replication to ``peers``
        peer hosts at every boundary (:meth:`replicate` is the manual
        spelling). ``demote_dir``/``durable_dir`` add the local-disk /
        durable rungs of async demotion (files land as
        ``{dir}/{prefix}{step}``, where ``checkpoint_io.recover`` and
        ``Manager.cold_start`` find them). Idempotent re-arm replaces
        the replicator config but keeps an existing store's images."""
        replica_id = self._b.view().replica_id
        scope = f"ram:{replica_id}"
        try:  # chaos scope = the served endpoint's identity when known
            netloc = urllib.parse.urlsplit(
                self._ckpt_server.address()).netloc
            if netloc:
                scope = f"ram:{netloc}"
        except Exception:  # noqa: BLE001 — duck-typed transports
            pass
        if store is None:
            store = (self.store
                     or RamCheckpointStore(keep=keep, chaos_scope=scope))
        self.store = store
        if demote_dir is not None:
            self._demote_dir = demote_dir
        self.replicator = RamReplicator(
            store, peers_fn=self._peers_fn, k=max(int(peers), 0),
            demote_dir=self._demote_dir, durable_dir=durable_dir,
            prefix=prefix, chaos_scope=scope, **self._replicator_kwargs)
        attach = getattr(self._ckpt_server, "attach_ram_store", None)
        if attach is not None:
            attach(store)
        logger.info(
            "%s: RAM checkpoint tier armed (k=%d demote_dir=%s "
            "durable_dir=%s)", replica_id, max(int(peers), 0),
            self._demote_dir, durable_dir)

    def disable(self) -> None:
        """Withdraw the tier: drain the in-flight replication, detach
        ``/ramckpt/*`` (peers' next probe 404s and rotates), and stop
        dispatching at boundaries. The store's images are dropped with
        it — a disabled tier must not serve stale steps."""
        rep, self.replicator = self.replicator, None
        if rep is not None:
            rep.shutdown()
        self.detach()
        if self.store is not None:
            self.store.clear()
        self.store = None

    def detach(self) -> None:
        """Stop serving/accepting the RAM rung (a draining or
        quarantined group): peers' next probe 404s and rotates."""
        detach = getattr(self._ckpt_server, "detach_ram_store", None)
        if detach is not None:
            detach()

    def shutdown(self) -> None:
        """Drain (or abandon, if stalled) the in-flight replication."""
        if self.replicator is not None:
            self.replicator.shutdown()

    def metrics(self) -> Dict[str, float]:
        """The store's and the replicator's own counters
        (``ram_ckpt_peers``, ``demote_stage_ms_total``, …) — present
        only while the tier is enabled."""
        out: Dict[str, float] = {}
        if self.store is not None:
            out.update(self.store.metrics())
        if self.replicator is not None:
            out.update(self.replicator.metrics())
        return out

    def replicate(self) -> Optional[Future]:
        """Commit-coupled RAM replication: snapshot the committed state
        and run the encode -> peer-push -> demote pipeline in the
        background; returns the job's Future (peer-accept count) or
        ``None`` when refused — an image of a state that is not a
        settled committed step's
        (:meth:`~torchft_tpu.boundary.Boundary.settled`) would multiply
        exactly the inconsistency the tier exists to escape."""
        if self.replicator is None:
            return None
        if not self._b.settled("RAM replication", "ram_replicate_skipped",
                               "ram_replicate_skip"):
            return None
        step = self._b.view().step
        # Spans the DISPATCH (on-device snapshot + enqueue); encode and
        # every demotion stage run on the replicator's worker and are
        # timed by its demote_*_ms counters.
        with self._b.tracer.span("ram_replicate", step=step):
            user_state, manager_state, meta = self._snapshot()
            fut = self.replicator.replicate_async(
                user_state, manager_state, meta=meta)
        self._b.log_event(event="ram_replicate", step=step)
        return fut

    def at_step_edge(self, committed: bool) -> None:
        """Dispatch this boundary's replication, surface a failed
        dispatch into the log/counters (the tier is best-effort — it
        must never take the training loop down with it), and detect
        replication-set collapse (peers accepting dropped to ZERO after
        replication had been landing) with a one-shot flight dump: the
        operator's signal that the fleet is one correlated failure away
        from the disk rung."""
        if self.replicator is None:
            return
        m = self.replicator.metrics()
        peers_now = m.get("ram_ckpt_peers", 0.0)
        if peers_now > 0:
            self._peers_seen = max(self._peers_seen, peers_now)
            self._collapse_dumped = False
        elif (self._peers_seen > 0
                and m.get("ram_ckpt_replications_total", 0.0) > 0
                and not self._collapse_dumped):
            v = self._b.view()
            self._collapse_dumped = True
            self._b.record(ram_replica_collapses_total=1)
            self._b.log_event(event="ram_replica_collapse", step=v.step,
                              peers_seen=self._peers_seen)
            self._b.flight_dump("ram_replica_collapse",
                                peers_seen=self._peers_seen)
            logger.error(
                "%s: RAM replication set collapsed (previously %d "
                "peer(s), now 0) — recovery is one correlated failure "
                "from the disk rung", v.replica_id,
                int(self._peers_seen))
        try:
            self.replicate()
        except Exception:  # noqa: BLE001 — best-effort tier
            v = self._b.view()
            self._b.record(ram_replicate_errors_total=1)
            self._b.log_event(event="ram_replicate_error", step=v.step)
            logger.warning(
                "%s: RAM replication dispatch failed at step %d",
                v.replica_id, v.step, exc_info=True)
