"""The exchange pipelines slices of bounded bytes, not whole leaves.

A leaf wider than ``manager._SLICE_BYTES`` on the wire is cut into
consecutive slices, each a bucket of one chunk that flows through
stage -> fetch -> ring -> put on its own
(docs/design/allreduce_pipeline.md, "Slices"). Here: the schedule's
geometry as pure metadata, then the whole pipeline over real socketpair
rings with the slice size patched small (mocked control plane, no native
library): numerics against the unsplit schedule, programs and
accumulators in the steady state, the staging window, the sharded
update's stripes, a healer's zeros and the int8 rung's residuals.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchft_tpu.exchange as exchange_mod
from torchft_tpu import policy as policy_mod
from torchft_tpu.exchange import (_PACK_STATS, GradExchange, ShardedGrads,
                                  _derive_schedule, _row_view,
                                  _zero_wire_chunk)
from torchft_tpu.manager import _zero_like

from mockplane import make_manager
from test_shard import _run_managers

SLICE = 1024  # bytes: what _SLICE_BYTES is patched to in this file


@pytest.fixture
def small_slices(monkeypatch):
    monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", SLICE)


# ------------------------------------------------------------ the schedule

METAS = {
    # one wide 2-D leaf: rows of 3, 85 rows a slice, a tail of 2 rows
    "wide2d": (((257, 3), "float32"),),
    # exact multiple: 4 slices of 256, no tail
    "exact": (((1024,), "float32"),),
    # a row wider than a slice: cut by elements
    "widerow": (((2, 700), "float32"),),
    # trailing axes kept whole as far as they fit: rows of 7*5
    "rank3": (((100, 7, 5), "float32"),),
    # small leaves around wide ones, a 0-size leaf, three dtypes
    "mixed": (((17, 3), "float32"), ((130,), "float32"),
              ((0, 5), "float32"), ((3000,), "float64"),
              ((5,), "float64"), ((6,), "int64"), ((600,), "int32"),
              ((40, 9), "float32"), ((2,), "float32")),
    # nothing wider than a slice
    "small": (((17, 3), "float32"), ((130,), "float32"),
              ((0, 5), "float32"), ((5,), "float64"), ((6,), "int64")),
}
WIRES = {"exact": None, "bf16": jnp.bfloat16}


def _old_make_buckets(sizes, bucket_bytes):
    """The schedule before slices: whole leaves, a bucket closes at
    >= bucket_bytes."""
    buckets, cur, cur_bytes = [], [], 0
    for i, nbytes in enumerate(sizes):
        cur.append(i)
        cur_bytes += int(nbytes)
        if cur_bytes >= bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _entries(sched):
    """(bucket, leaf, offset, count, chunk) of every entry."""
    return [(b, i, off, n, c)
            for b, cs in enumerate(sched.chunks) for c in cs
            for i, off, n in zip(c.idx, c.offs, c.sizes)]


@pytest.mark.usefixtures("small_slices")
class TestSliceSchedule:
    @pytest.mark.parametrize("wire", list(WIRES))
    @pytest.mark.parametrize("name", list(METAS))
    def test_every_element_once_in_leaf_order(self, name, wire):
        metas = METAS[name]
        sched = _derive_schedule(metas, 256, WIRES[wire])
        by_leaf = {}
        for _b, i, off, n, _c in _entries(sched):
            by_leaf.setdefault(i, []).append((off, n))
        assert sorted(by_leaf) == list(range(len(metas)))
        for i, (shape, _) in enumerate(metas):
            pos = 0
            for off, n in by_leaf[i]:  # consecutive, in bucket order
                assert off == pos
                pos += n
            assert pos == int(np.prod(shape))
        # leaf order kept from bucket to bucket
        flat = [i for idx in sched.buckets for i in idx]
        assert flat == sorted(flat)
        for cs in sched.chunks:
            for c in cs:
                assert c.total == sum(c.sizes)

    @pytest.mark.parametrize("wire", list(WIRES))
    @pytest.mark.parametrize("name", list(METAS))
    def test_slices_are_bounded_whole_rows_alone_in_a_bucket(self, name,
                                                             wire):
        metas = METAS[name]
        sched = _derive_schedule(metas, 256, WIRES[wire])
        per_leaf = {}
        for b, i, off, n, c in _entries(sched):
            size = int(np.prod(metas[i][0]))
            nbytes = n * c.wire.itemsize
            if c.rows is None:
                # a whole leaf, and only one that fits a slice
                assert (off, n) == (0, size) and nbytes <= SLICE
                continue
            assert nbytes <= SLICE < size * c.wire.itemsize
            assert len(sched.chunks[b]) == 1 and len(c.idx) == 1
            lead, rows = _row_view(c.shapes[0], c.wire.itemsize, SLICE)
            row = int(np.prod(c.shapes[0][lead:]))
            assert row * c.wire.itemsize <= SLICE
            assert c.rows == (lead, off // row, n // row)
            assert off % row == 0 and n % row == 0
            per_leaf.setdefault(i, []).append(n)
        assert {i: len(v) for i, v in per_leaf.items()} == sched.slices
        for sizes in per_leaf.values():
            # equal slices, the last one shorter or equal
            assert len(set(sizes[:-1])) <= 1 and sizes[-1] <= sizes[0]
            assert len(sizes) >= 2

    @pytest.mark.parametrize("wire", list(WIRES))
    def test_leaves_under_a_slice_keep_the_old_schedule(self, wire,
                                                        monkeypatch):
        metas = METAS["small"]
        got = _derive_schedule(metas, 256, WIRES[wire])
        wdt = None if WIRES[wire] is None else np.dtype(WIRES[wire])
        adv = [int(np.prod(s) or 1)
               * exchange_mod._wire_pair(dt, wdt)[1].itemsize
               for s, dt in metas]
        assert got.buckets == _old_make_buckets(adv, 256)
        assert not got.slices
        assert all(c.rows is None and not any(c.offs)
                   for cs in got.chunks for c in cs)
        # and the slice size is nothing to it
        monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", 1 << 40)
        wide = _derive_schedule(metas, 256, WIRES[wire])
        assert wide.fingerprint == got.fingerprint

    @pytest.mark.parametrize("name", ["wide2d", "mixed"])
    def test_fingerprint_carries_the_cut(self, name, monkeypatch):
        metas = METAS[name]
        split = _derive_schedule(metas, 256, None)
        again = _derive_schedule(metas, 256, None)
        assert split.fingerprint == again.fingerprint
        assert split.buckets == again.buckets
        monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", 2 * SLICE)
        other = _derive_schedule(metas, 256, None)
        monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", 1 << 40)
        unsplit = _derive_schedule(metas, 256, None)
        assert not unsplit.slices
        assert len({split.fingerprint, other.fingerprint,
                    unsplit.fingerprint}) == 3

    @pytest.mark.parametrize("wire", list(WIRES))
    def test_every_role_derives_one_geometry(self, wire, exchange_rig):
        """Participant (device leaves), healer and spare (host zeros)
        land on ONE cached schedule, and the zero contribution of each
        chunk has the slice's length and wire dtype."""
        x = exchange_rig(bucket_bytes=256, wire_dtype=WIRES[wire]).x
        tree = {"a": jnp.ones((257, 3), jnp.float32),
                "b": jnp.zeros((40,), jnp.float32),
                "i": jnp.arange(600, dtype=jnp.int32)}
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        part = x.schedule(treedef, leaves)
        heal = x.schedule(treedef, [_zero_like(x) for x in leaves])
        assert part is heal
        assert part.slices == {0: 4 if wire == "exact" else 2, 2: 3}
        for cs in part.chunks:
            for c in cs:
                z = _zero_wire_chunk(c, False)
                assert z.shape == (c.total,) and z.dtype == c.wire
                assert not z.any()

    def test_schedule_cache_is_keyed_by_the_slice_size(self, monkeypatch,
                                                       exchange_rig):
        x = exchange_rig(bucket_bytes=256).x
        leaves, treedef = jax.tree_util.tree_flatten(
            {"a": np.ones((257, 3), np.float32)})
        split = x.schedule(treedef, leaves)
        monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", 1 << 40)
        assert x.schedule(treedef, leaves) is not split


# -------------------------------------------------- the pipeline, end to end

def _host_tree(rank, step=0):
    """A tree with leaves of 4, 4, 2 and 3 slices (the last on the
    host), small ones, an int leaf of 3 slices and an empty one."""
    def f(i, shape):
        return np.random.default_rng([step, rank, i]).normal(
            size=shape).astype(np.float32)
    return {
        "a": f(0, (257, 3)), "b": f(1, (1000,)), "c": f(2, (40, 9)),
        "e": np.zeros((0, 5), np.float32),
        "h": f(3, (50, 11)),
        "i": np.arange(600, dtype=np.int32) * (rank + 1),
        "s": f(4, (10,)),
    }


HOST_KEYS = ("h",)
SPLIT = {"a": 4, "b": 4, "c": 2, "h": 3, "i": 3}


def _tree(rank, step=0):
    return {k: (v if k in HOST_KEYS else jnp.asarray(v))
            for k, v in _host_tree(rank, step).items()}


def _np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _allreduce_body(steps=1, barrier=None):
    def body(m, rank):
        outs = []
        for step in range(steps):
            m.step()
            got = m.allreduce(_tree(rank, step)).result(timeout=60)
            assert m.errored() is None, m.errored()
            assert m.should_commit()
            if barrier is not None:
                barrier.wait(timeout=60)
            outs.append((_np(got), m.metrics(), dict(_PACK_STATS)))
        return outs
    return body


MKW = {"allreduce_bucket_bytes": 256}


class TestSlicedAllreduce:
    @pytest.mark.parametrize("world", [2, 3, 4])
    def test_average_of_a_split_tree(self, world, monkeypatch):
        monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", SLICE)
        out = _run_managers(world, _allreduce_body(), MKW)
        res = [o[0][0] for o in out]
        hosts = [_host_tree(r) for r in range(world)]
        for k in hosts[0]:
            for r in range(1, world):  # every group the same bits
                np.testing.assert_array_equal(res[r][k], res[0][k])
            assert res[0][k].shape == hosts[0][k].shape
            assert res[0][k].dtype == hosts[0][k].dtype
            if k == "i":
                want = sum(h[k] for h in hosts) // world
                np.testing.assert_array_equal(res[0][k], want)
            else:
                want = sum(h[k].astype(np.float64) for h in hosts) / world
                np.testing.assert_allclose(res[0][k], want, rtol=2e-6,
                                           atol=1e-6)
        mx = out[0][0][1]
        sched_ops = mx["allreduce_ring_ops_total"]
        assert mx["allreduce_split_slices_total"] == sum(SPLIT.values())
        assert sched_ops > mx["allreduce_split_slices_total"]
        if world > 2:
            return
        # Two groups: bitwise what the unsplit schedule gives (a + b).
        monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", 1 << 40)
        whole = _run_managers(world, _allreduce_body(), MKW)
        assert whole[0][0][1]["allreduce_split_slices_total"] == 0
        assert whole[0][0][1]["allreduce_ring_ops_total"] < sched_ops
        for k in hosts[0]:
            np.testing.assert_array_equal(whole[0][0][0][k], res[0][k])

    @pytest.mark.parametrize("world", [2, 3])
    def test_steady_steps_compile_nothing_and_pool_accumulators(
            self, world, small_slices):
        """From the second step on no pack or put program is traced, and
        the ring never owns more accumulators than one step has ops:
        equal slices share them by (dtype, size), within a step too."""
        steps = 3
        out = _run_managers(
            world, _allreduce_body(steps, threading.Barrier(world)), MKW)
        for rank in range(world):
            per_step = out[rank]
            ops = per_step[0][1]["allreduce_ring_ops_total"]
            for s in range(steps):
                _res, mx, stats = per_step[s]
                assert mx["allreduce_ring_ops_total"] == ops * (s + 1)
                assert mx["allreduce_split_slices_total"] == (
                    sum(SPLIT.values()) * (s + 1))
                assert mx["allreduce_host_copy_bytes_total"] == \
                    per_step[0][1]["allreduce_host_copy_bytes_total"] * (
                        s + 1)
                # every op's chunk took an accumulator: kept or fresh
                assert (mx["allreduce_accum_reuse_total"]
                        + mx["allreduce_accum_alloc_total"]) == ops * (s + 1)
                assert mx["allreduce_accum_alloc_total"] <= ops
                assert stats["pack_cache_misses"] == \
                    per_step[0][2]["pack_cache_misses"]
                assert stats["put_cache_misses"] == \
                    per_step[0][2]["put_cache_misses"]
            # results of earlier steps stay what they were while later
            # steps fold into the same accumulators
            for s in range(steps):
                hosts = [_host_tree(r, s) for r in range(world)]
                if world == 2:
                    np.testing.assert_array_equal(
                        per_step[s][0]["a"],
                        (hosts[0]["a"] + hosts[1]["a"]) / 2)
                np.testing.assert_array_equal(per_step[s][0]["a"],
                                              out[0][s][0]["a"])

    def test_two_programs_a_split_leaf(self, small_slices, exchange_rig):
        """One exchange, one thread, leaf shapes no other test uses: the
        staging and the put of every slice trace two pack and two put
        programs a split leaf (full slices, tail) — one where the
        slices divide the leaf — however many slices it has."""
        tree = {"t": jnp.ones((263, 5), jnp.float32),     # 6 slices, tail
                "u": jnp.ones((5, 256), jnp.float32),     # 5 slices, none
                "v": jnp.ones((3001,), jnp.float32)}      # 12 slices, tail
        x = exchange_rig(bucket_bytes=256).x
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        sched = x.schedule(treedef, leaves)
        assert sched.slices == {0: 6, 1: 5, 2: 12}
        before = dict(_PACK_STATS)
        asm = {i: [None, k] for i, k in sched.slices.items()}
        done = {}
        for b, chunks in enumerate(sched.chunks):
            recs = x._stage_bucket(chunks, leaves, bucket=b, sched=sched)
            bufs = x._wait_bucket(recs, leaves, bucket=b)
            done.update(x._put_bucket_chunks(
                chunks, [np.array(a) for a in bufs], leaves, 1, asm))
        assert _PACK_STATS["pack_cache_misses"] \
            - before["pack_cache_misses"] == 2 + 1 + 2
        assert _PACK_STATS["put_cache_misses"] \
            - before["put_cache_misses"] == 2 + 1 + 2
        assert not asm and sorted(done) == [0, 1, 2]
        for i, leaf in enumerate(leaves):
            np.testing.assert_array_equal(np.asarray(done[i]),
                                          np.asarray(leaf))
            assert done[i].sharding == leaf.sharding

    @pytest.mark.parametrize("window", ["0", "1", None])
    def test_stage_ahead_counts_slices(self, window, small_slices,
                                       monkeypatch):
        """TORCHFT_ALLREDUCE_STAGE_AHEAD=K stages K units beyond the
        one waited on, and a unit is a slice: at 0 one slice's packed
        copy is on the device at a time."""
        if window is None:
            monkeypatch.delenv("TORCHFT_ALLREDUCE_STAGE_AHEAD",
                               raising=False)
        else:
            monkeypatch.setenv("TORCHFT_ALLREDUCE_STAGE_AHEAD", window)
        log = {}
        stage, wait = GradExchange._stage_bucket, GradExchange._wait_bucket

        def staged(self, chunks, leaves, bucket=-1, **kw):
            recs = stage(self, chunks, leaves, bucket=bucket, **kw)
            held = sum(int(p.nbytes) for _c, _d, p, _k in recs
                       if p is not None)
            log.setdefault(id(self), []).append(("stage", bucket, held))
            return recs

        def waited(self, recs, leaves, bucket=-1):
            log.setdefault(id(self), []).append(("wait", bucket, 0))
            return wait(self, recs, leaves, bucket=bucket)

        monkeypatch.setattr(GradExchange, "_stage_bucket", staged)
        monkeypatch.setattr(GradExchange, "_wait_bucket", waited)
        _run_managers(2, _allreduce_body(), MKW)
        assert len(log) == 2
        for events in log.values():
            n = sum(1 for e in events if e[0] == "stage")
            assert n == sum(1 for e in events if e[0] == "wait") > 16
            ahead = most = 0
            for what, _b, held in events:
                ahead += 1 if what == "stage" else -1
                most = max(most, ahead)
                assert held <= SLICE
            assert [e[1] for e in events if e[0] == "stage"] == list(range(n))
            assert most == (n if window is None else int(window) + 1)

    def test_healer_contributes_zero_slices(self, small_slices):
        """A healing group sends zeros of the slices' geometry and gets
        the participant's gradients back, unscaled and whole."""
        def body(m, rank):
            m.step()
            got = m.allreduce(_tree(rank)).result(timeout=60)
            assert m.errored() is None, m.errored()
            return _np(got), m.metrics()

        out = _run_managers(2, body, MKW, heal_ranks=(1,))
        want = _host_tree(0)
        for rank in range(2):
            for k, v in want.items():
                np.testing.assert_array_equal(out[rank][0][k], v)
        assert out[1][1]["allreduce_split_slices_total"] == \
            sum(SPLIT.values())
        # the healer fetched nothing from its device
        assert out[1][1]["allreduce_wire_bytes_total"] == 0


class TestSlicedShardedUpdate:
    @pytest.mark.parametrize("world", [2, 3])
    def test_stripes_and_params_follow_the_slices(self, world,
                                                  small_slices):
        def rs_body(m, rank):
            m.step()
            sg = m.reduce_scatter(_tree(rank)).result(timeout=60)
            assert m.errored() is None, m.errored()
            assert isinstance(sg, ShardedGrads)
            return sg

        ar = [o[0][0] for o in _run_managers(world, _allreduce_body(), MKW)]
        rs = _run_managers(world, rs_body, MKW)
        leaves_ar = jax.tree_util.tree_leaves(ar[0])
        assert any(c.rows is not None for c in rs[0].chunks)
        # Every rank's stripes, concatenated chunk by chunk, are the
        # allreduce's leaves cut where the schedule cut them.
        for k, c in enumerate(rs[0].chunks):
            full = np.concatenate([np.asarray(rs[r].shards[k])
                                   for r in range(world)])
            want = np.concatenate([
                np.ravel(leaves_ar[i])[off:off + n]
                for i, off, n in zip(c.idx, c.offs, c.sizes)]
                or [np.empty(0, c.orig)])
            np.testing.assert_array_equal(full, want)
        # The parameters' stripes follow the same cut, and gathering
        # them gives the parameters back: device and host leaves alike.
        params = jax.tree_util.tree_map(
            lambda g, t: jnp.asarray(g) if isinstance(t, jax.Array) else g,
            ar[0], _tree(0))
        gathered = [rs[r].param_shards(params) for r in range(world)]
        for r in range(world):
            assert [int(p.size) for p in gathered[r]] == \
                [int(np.size(s)) for s in rs[r].shards]
        back = rs[0].assemble_params(gathered, params)
        for k, v in ar[0].items():
            np.testing.assert_array_equal(np.asarray(back[k]), v)
            assert isinstance(back[k], jax.Array) == (k not in HOST_KEYS)

    def test_full_shards_follow_the_slices(self, small_slices):
        m = make_manager(allreduce_bucket_bytes=256, shard_update=True)
        try:
            sg = m.full_shards(_tree(0))
            assert (sg.rank, sg.world) == (0, 1)
            flat = jax.tree_util.tree_leaves(_host_tree(0))
            assert any(c.rows is not None for c in sg.chunks)
            for c, shard in zip(sg.chunks, sg.shards):
                want = np.concatenate([
                    np.ravel(flat[i])[off:off + n]
                    for i, off, n in zip(c.idx, c.offs, c.sizes)]
                    or [np.empty(0, c.orig)])
                np.testing.assert_array_equal(shard, want)
        finally:
            m.shutdown()


class TestSlicedInt8:
    @pytest.mark.parametrize("device_quantize", [True, False],
                             ids=["device", "host"])
    def test_residuals_are_kept_a_slice(self, device_quantize,
                                        small_slices):
        """int8 + error feedback: a slice is a chunk, so its residual
        is keyed (fingerprint, bucket, chunk) and has the slice's
        length; the second step folds it back in."""
        int8 = next(p for p in policy_mod.LADDER if p.name == "sync-int8")

        def body(m, rank):
            outs = []
            for step in range(2):
                m.step()
                got = m.allreduce(_tree(rank, step)).result(timeout=60)
                assert m.errored() is None, m.errored()
                assert m.should_commit()
                outs.append(_np(got))
            leaves, treedef = jax.tree_util.tree_flatten(_tree(rank))
            sched = m._exchange.schedule(treedef, leaves)
            store = dict(m._exchange._dev_residuals)
            store.update(m._exchange._ef_residuals)
            sizes = {k[1:]: int(np.shape(v)[0]) for k, v in store.items()}
            assert all(k[0] == sched.fingerprint for k in store)
            return outs, sizes, sched

        out = _run_managers(
            2, body, dict(MKW, policy=int8,
                          device_quantize=device_quantize))
        outs, sizes, sched = out[0]
        want = {(b, j): c.total
                for b, cs in enumerate(sched.chunks)
                for j, c in enumerate(cs)
                if np.issubdtype(c.orig, np.floating)}
        assert sizes == want
        assert sum(1 for b, _j in want
                   if sched.chunks[b][0].rows is not None) == \
            sum(v for k, v in SPLIT.items() if k != "i")
        for step in range(2):
            hosts = [_host_tree(r, step) for r in range(2)]
            for k in ("a", "b", "c", "h", "s"):
                np.testing.assert_array_equal(out[0][0][step][k],
                                              out[1][0][step][k])
                mean = (hosts[0][k] + hosts[1][k]) / 2
                # one int8 step of each contribution's range
                tol = sum(np.ptp(h[k]) for h in hosts) / 254
                np.testing.assert_allclose(out[0][0][step][k], mean,
                                           atol=tol)
            np.testing.assert_array_equal(
                out[0][0][step]["i"], (hosts[0]["i"] + hosts[1]["i"]) // 2)
