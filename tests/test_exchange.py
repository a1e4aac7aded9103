"""The cross-group exchange on its own (torchft_tpu/exchange.py): a
:class:`GradExchange` over an in-memory fold communicator, with no
``Manager`` and no control plane, and the seam the module was cut along.

The ``exchange_rig`` fixture (conftest.py) is also what other test files
use when they only need a schedule, a pack, a stage, a wait or a put.
"""

import ast
import threading
from collections import Counter
from concurrent.futures import Future
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchft_tpu.exchange as exchange_mod
from torchft_tpu.communicator import DummyCommunicator, _slice_shards
from torchft_tpu.exchange import ShardedGrads, StepFacts
from torchft_tpu.utils import div_by_count

from test_transport import _FoldComm, _FoldHub

FACTS = StepFacts(participating=True, n=2, int8=False)


class _SeenComm(_FoldComm):
    """The fold communicator, recording every wire op in submission
    order and every hand-back (``release_wire_buffers``)."""

    def __init__(self, hub, rank):
        super().__init__(hub, rank)
        self.ops = []        # (kind, [elements a buffer])
        self.released = []   # None, or the ids of the arrays handed back
        self.lent = []       # ids of every array an op resolved to

    def _seen(self, kind, buffers):
        self.ops.append((kind, [int(np.size(b)) for b in buffers]))

    def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
        self._seen("ar", buffers)
        fut = super().allreduce_wire(buffers, orig_dtypes, op)
        fut.add_done_callback(
            lambda f: self.lent.append([id(a) for a in f.result()]))
        return fut

    def reduce_scatter_wire(self, buffers, orig_dtypes, op="sum"):
        # As the ABC's default: this rank's stripe of the same fold.
        self._seen("rs", buffers)
        out = Future()
        _FoldComm.allreduce_wire(self, buffers, orig_dtypes, op) \
            .add_done_callback(lambda f: out.set_result(
                _slice_shards(f.result(), self.rank(), self.size())))
        return out

    def release_wire_buffers(self, buffers):
        self.released.append(None if buffers is None
                             else [id(b) for b in buffers])


class _ReverseComm(_SeenComm):
    """Resolves a step's ``n_ops`` wire ops in the reverse of the order
    they were submitted in, once all of them are folded: the furthest a
    communicator with several lanes can stray from submission order."""

    def __init__(self, hub, rank, n_ops):
        super().__init__(hub, rank)
        self.n_ops = n_ops
        self.held = []
        self.resolved = []   # ordinals within the step, as resolved
        self.settlers = []

    def _late(self, inner):
        outer = Future()
        self.held.append((inner, outer))
        if len(self.held) == self.n_ops:
            batch, self.held = self.held, []
            t = threading.Thread(target=self._settle, args=(batch,))
            self.settlers.append(t)
            t.start()
        return outer

    def _settle(self, batch):
        results = [inner.result(timeout=60) for inner, _ in batch]
        for i in reversed(range(len(batch))):
            self.resolved.append(i)
            batch[i][1].set_result(results[i])

    def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
        return self._late(super().allreduce_wire(buffers, orig_dtypes, op))

    def reduce_scatter_wire(self, buffers, orig_dtypes, op="sum"):
        return self._late(
            super().reduce_scatter_wire(buffers, orig_dtypes, op))


def _tree(rank):
    """Device and host leaves, two dtypes, and (under the file's 1 KiB
    slices) two split leaves."""
    rng = np.random.default_rng(7 + rank)
    return {"wide": jnp.asarray(rng.normal(size=(257, 3)), jnp.float32),
            "host": rng.normal(size=(700,)).astype(np.float32),
            "small": jnp.asarray(rng.normal(size=(40,)), jnp.float32),
            "ints": jnp.arange(6, dtype=jnp.int32) * (rank + 1)}


def _run_world(op, rigs, steps=1):
    """``steps`` steps of ``op`` on every rig at once; per rank the
    list of results."""
    out = [[] for _ in rigs]
    errors = []

    def run(rank):
        try:
            for _ in range(steps):
                out[rank].append(rigs[rank].run(op, _tree(rank), FACTS)[0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=run, args=(r,))
          for r in range(len(rigs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in ts), "exchange rig deadlocked"
    assert not errors, errors
    return out


@pytest.fixture
def small_slices(monkeypatch):
    monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", 1024)


def _pair(exchange_rig, **kw):
    hub = _FoldHub()
    comms = [_SeenComm(hub, r) for r in range(2)]
    return comms, [exchange_rig(c, bucket_bytes=256, **kw) for c in comms]


def _stripes_as_tree(sgs):
    """Every rank's stripes of a reduce-scatter, concatenated chunk by
    chunk and put back into leaves."""
    sg = sgs[0]
    gathered = [[np.asarray(s) for s in g.shards] for g in sgs]
    zeros = jax.tree_util.tree_unflatten(
        sg.treedef, [np.zeros(np.shape(x), x.dtype) for x in sg.leaves])
    return sg.assemble_params(gathered, zeros)


class TestExchangeAlone:
    @pytest.mark.parametrize("op", ["allreduce", "reduce_scatter"])
    def test_both_ops_drive_one_loop(self, op, exchange_rig,
                                     small_slices):
        """End to end with no Manager: the averaged tree (allreduce) or
        the stripes of it (reduce-scatter, concatenated) are bitwise
        the plain mean; ring ops go out in schedule order, one a
        bucket; spans and counters are those of the other op."""
        comms, rigs = _pair(exchange_rig)
        got = [r[0] for r in _run_world(op, rigs)]
        want = jax.tree_util.tree_map(
            lambda a, b: div_by_count(np.asarray(a) + np.asarray(b), 2),
            _tree(0), _tree(1))
        if op == "reduce_scatter":
            assert all(isinstance(g, ShardedGrads) for g in got)
            assert [g.rank for g in got] == [0, 1]
            got = [_stripes_as_tree(got)] * 2
        for g in got:
            for k, w in want.items():
                np.testing.assert_array_equal(np.asarray(g[k]), w)
        leaves, treedef = jax.tree_util.tree_flatten(_tree(0))
        sched = rigs[0].x.schedule(treedef, leaves)
        assert len(sched.slices) == 2 and len(sched.chunks) > 4
        kind = "ar" if op == "allreduce" else "rs"
        for c, rig in zip(comms, rigs):
            assert c.ops == [(kind, [ch.total for ch in cs])
                             for cs in sched.chunks]
            n = len(sched.chunks)
            stages = Counter(s["stage"] for s in rig.tracer.spans())
            assert stages == {"fetch_dispatch": n, "fetch_wait": n,
                              "put": n}
            assert rig.counters["allreduce_ring_ops_total"] == n
            assert rig.counters["allreduce_split_slices_total"] == sum(
                sched.slices.values())
            assert rig.counters["allreduce_count"] == 1
            assert rig.counters["reduce_scatter_count"] == (
                op == "reduce_scatter")
            for key in ("allreduce_ms_total", "allreduce_ring_ms_total",
                        "allreduce_put_ms_total",
                        "allreduce_fetch_ms_total",
                        "allreduce_wire_bytes_total"):
                assert rig.counters[key] > 0, key

    @pytest.mark.parametrize("op", ["allreduce", "reduce_scatter"])
    def test_ops_may_complete_in_any_order(self, op, exchange_rig,
                                           small_slices):
        """A ring of several lanes finishes ops out of submission order.
        Buckets settle by slot and a split leaf is assembled by row
        offset, so a communicator that resolves every step's ops in
        REVERSE gives the same averaged tree, split leaves included,
        two steps running (the second folds into handed-back
        buffers)."""
        leaves, treedef = jax.tree_util.tree_flatten(_tree(0))
        hub = _FoldHub()
        probe = exchange_rig(_SeenComm(hub, 0), bucket_bytes=256)
        sched = probe.x.schedule(treedef, leaves)
        n = len(sched.chunks)
        assert len(sched.slices) == 2 and n > 4
        comms = [_ReverseComm(hub, r, n) for r in range(2)]
        rigs = [exchange_rig(c, bucket_bytes=256) for c in comms]
        steps = _run_world(op, rigs, steps=2)
        want = jax.tree_util.tree_map(
            lambda a, b: div_by_count(np.asarray(a) + np.asarray(b), 2),
            _tree(0), _tree(1))
        for step in range(2):
            got = [r[step] for r in steps]
            if op == "reduce_scatter":
                got = [_stripes_as_tree(got)] * 2
            for g in got:
                for k, w in want.items():
                    np.testing.assert_array_equal(np.asarray(g[k]), w)
        kind = "ar" if op == "allreduce" else "rs"
        for c, rig in zip(comms, rigs):
            for t in c.settlers:
                t.join(timeout=30)
            assert c.resolved == list(reversed(range(n))) * 2
            assert c.ops == [(kind, [ch.total for ch in cs])
                             for cs in sched.chunks] * 2
            assert rig.counters["allreduce_ring_ops_total"] == 2 * n
            assert rig.counters["allreduce_count"] == 2
            if op == "allreduce":
                # every bucket's buffers still come back, whatever the
                # order, before the step resolves
                handed = [r for r in c.released if r is not None]
                assert sorted(map(tuple, handed)) == sorted(
                    map(tuple, c.lent))

    def test_ops_agree_on_spans_and_counts(self, exchange_rig,
                                           small_slices):
        """The two ops differ in the communicator's op and in what a
        reduced bucket becomes, and in nothing the tracer or the
        counters see: same span stages with the same tags, same
        counter keys but ``reduce_scatter_count``."""
        seen = {}
        for op in ("allreduce", "reduce_scatter"):
            _, rigs = _pair(exchange_rig)
            _run_world(op, rigs)
            spans = sorted(
                (s["stage"], tuple(sorted(
                    (k, v) for k, v in s.items()
                    if k in ("bucket", "chunks", "bytes"))))
                for s in rigs[0].tracer.spans())
            seen[op] = (spans, set(rigs[0].counters))
        assert seen["allreduce"][0] == seen["reduce_scatter"][0]
        assert seen["reduce_scatter"][1] - seen["allreduce"][1] == {
            "reduce_scatter_count"}
        assert not seen["allreduce"][1] - seen["reduce_scatter"][1]

    @pytest.mark.parametrize("op", ["allreduce", "reduce_scatter"])
    def test_hand_back_is_the_allreduces_alone(self, op, exchange_rig,
                                               small_slices):
        """What PR 28 gave the allreduce and the parent's
        reduce-scatter never did, pinned: an allreduce drops the kept
        accumulators when the gradient signature changes and hands
        every bucket's reduced buffers back before the step resolves;
        a reduce-scatter (whose ring keeps its accumulator inside the
        op and resolves to copies) tells the communicator nothing."""
        comms, rigs = _pair(exchange_rig)
        _run_world(op, rigs, steps=2)
        for c in comms:
            if op == "reduce_scatter":
                assert c.released == []
                continue
            # one drop at the first signature, then one hand-back a
            # bucket of exactly what its op resolved to, all of them
            # in before the step's future resolved
            assert c.released[0] is None
            assert None not in c.released[1:]
            assert sorted(c.released[1:]) == sorted(c.lent)
            assert len(c.lent) == len(c.ops)

    def test_failed_step_defaults(self, exchange_rig):
        """The exchange returns the raw future and what a failed step
        resolves to: the input tree, or zero stripes with the real
        geometry; swallowing the error is the caller's."""

        class Boom(DummyCommunicator):
            def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
                raise RuntimeError("boom")

        rig = exchange_rig(Boom(rank=1, world_size=2))
        tree = {"g": np.arange(10, dtype=np.float32)}
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        with pytest.raises(RuntimeError):
            rig.x.allreduce(FACTS, tree, leaves, treedef)

        class Late(DummyCommunicator):
            def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
                f = Future()
                f.set_exception(RuntimeError("late"))
                return f

        rig = exchange_rig(Late(rank=1, world_size=2))
        fut, default_fn = rig.x.allreduce(FACTS, tree, leaves, treedef)
        with pytest.raises(RuntimeError):
            fut.result(timeout=10)
        assert default_fn() is tree
        fut, default_fn = rig.x.reduce_scatter(FACTS, tree, leaves,
                                               treedef)
        with pytest.raises(RuntimeError):
            fut.result(timeout=10)
        sg = default_fn()
        assert isinstance(sg, ShardedGrads) and (sg.rank, sg.world) == (1, 2)
        assert [s.tolist() for s in sg.shards] == [[0.0] * 5]

    def test_set_wire_flushes_on_a_rung_change_only(self, exchange_rig):
        rig = exchange_rig()
        rig.x._ef_residuals[("fp", 0, 0)] = np.ones(4, np.float32)
        rig.x._dev_residuals[("fp", 0, 1)] = jnp.ones(4, jnp.float32)
        rig.x.set_wire(0, jnp.bfloat16)   # same rung: dtype only
        assert str(rig.x.wire_dtype) == "bfloat16"
        assert rig.x._ef_residuals and rig.x._dev_residuals
        assert rig.gauge == []
        rig.x.set_wire(2, None)
        assert rig.x.wire_dtype is None
        assert not rig.x._ef_residuals and not rig.x._dev_residuals
        assert rig.gauge == [0.0]


class TestSeam:
    def test_exchange_imports_nothing_from_manager(self):
        """Arrows point one way: manager.py -> exchange.py ->
        communicator.py."""
        src = Path(exchange_mod.__file__).read_text()
        mods = set()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.Import):
                mods.update(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                mods.add(node.module or "")
                mods.update(f"{node.module}.{a.name}" for a in node.names)
        assert not [m for m in mods if m.startswith("torchft_tpu.manager")]
        ours = {m.split(".")[1] for m in mods
                if m.startswith("torchft_tpu.")}
        assert ours <= {"communicator", "utils"}, ours

    def test_manager_keeps_no_alias_of_a_moved_name(self):
        import torchft_tpu.manager as manager_mod
        from torchft_tpu.manager import Manager

        moved = ("_derive_schedule", "_pack_leaves", "_pack_fn",
                 "_put_slice", "_row_view", "_zero_wire_chunk",
                 "_SLICE_BYTES", "_PACK_STATS", "_device_quantize_pack",
                 "_transfer_dtype", "_wire_pair", "_make_buckets",
                 "_stage_ahead_window", "_ChunkPlan", "_AllreduceSchedule")
        assert not [n for n in moved if hasattr(manager_mod, n)]
        gone = ("_get_schedule", "_stage_bucket", "_wait_bucket",
                "_put_bucket_chunks", "_int8_quantize_bucket",
                "_host_allreduce_pipelined",
                "_host_reduce_scatter_pipelined")
        assert not [n for n in gone if hasattr(Manager, n)]
