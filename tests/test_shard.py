"""Cross-replica sharding tests (docs/design/sharded_update.md;
``scripts/test.sh shard``): the ZeRO-style reduce-scatter weight update
(transport numerics, wrapper forwarding, Manager pipeline, FTOptimizer
stripe apply), the torrent-striped multi-donor heal, and the sharded
durable checkpoint format. All tier-1 — socketpair rings and real HTTP
on loopback, no native library."""

import os
import threading
import urllib.parse
import urllib.request
from unittest.mock import MagicMock, patch

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mockplane import make_manager, quorum_result
from test_manager import _make_test_rings, _wired_comm
from torchft_tpu import chaos
from torchft_tpu.backends.host import HostCommunicator, _Ring
from torchft_tpu.chaos import ChaosSchedule, EndpointChaos
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.communicator import (Communicator, _slice_shards,
                                      shard_bounds)
from torchft_tpu.exchange import ShardedGrads
from torchft_tpu.manager import _stripe_seed
from torchft_tpu.optim import FTOptimizer

pytestmark = pytest.mark.shard


class _Holder:
    """Minimal FTOptimizer holder (the trainer duck type)."""

    def __init__(self, params, opt_state=None):
        self.params = params
        self.opt_state = opt_state


# ----------------------------------------------------------- geometry

class TestShardBounds:
    def test_partition_covers_exactly(self):
        for size in (0, 1, 7, 100, 101):
            for world in (1, 2, 3, 5, 8):
                b = shard_bounds(size, world)
                assert b[0] == 0 and b[-1] == size
                assert all(b[i] <= b[i + 1] for i in range(world))

    def test_slice_shards_concat_roundtrip(self):
        x = np.arange(103, dtype=np.float32)
        world = 4
        parts = [_slice_shards([x], r, world)[0] for r in range(world)]
        np.testing.assert_array_equal(np.concatenate(parts), x)
        # Copies, not views: callers own the shards outright.
        parts[0][:] = -1
        assert x[0] == 0

    def test_same_geometry_as_exact_ring_chunking(self):
        # The ONE-geometry invariant: the exact ring reduce-scatter's
        # stripe must equal shard_bounds' stripe, or reassembled params
        # tear at seams.
        b = shard_bounds(1000, 3)
        np.testing.assert_array_equal(
            b, np.linspace(0, 1000, 4, dtype=np.int64))


# ----------------------------------------------- transport numerics

def _run_ring(world, fn):
    rings = _make_test_rings(world)
    comms = []
    for r in range(world):
        c = HostCommunicator(timeout_sec=15)
        c._rank, c._world = r, world
        comms.append(c)
    out = [None] * world
    errors = []

    def w(r):
        try:
            out[r] = fn(comms[r], rings[r], r)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=w, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    alive = [t for t in ts if t.is_alive()]
    for ring in rings:
        ring.close()
    assert not alive, "ring deadlocked"
    assert not errors, errors
    return out, comms


class TestReduceScatterWireTransport:
    """``_do_reduce_scatter_wire`` over real sockets: concat of every
    rank's stripe must be BITWISE identical to ``_do_allreduce_wire`` —
    the invariant making the ZeRO update's allgathered params equal the
    allreduce path's."""

    @pytest.mark.parametrize("world", [2, 3, 5])
    def test_exact_bitwise_vs_allreduce(self, world):
        rng = np.random.default_rng(world)
        x = [rng.normal(size=10_007).astype(np.float32)
             for _ in range(world)]
        ar, _ = _run_ring(world, lambda c, ring, r: c._do_allreduce_wire(
            ring, [x[r].copy()], [np.dtype(np.float32)], "sum"))
        rs, _ = _run_ring(
            world, lambda c, ring, r: c._do_reduce_scatter_wire(
                ring, [x[r].copy()], [np.dtype(np.float32)], "sum"))
        full = np.concatenate([rs[r][0] for r in range(world)])
        np.testing.assert_array_equal(full, ar[0][0])
        b = shard_bounds(x[0].size, world)
        for r in range(world):
            assert rs[r][0].size == int(b[r + 1] - b[r])

    @pytest.mark.parametrize("world", [2, 3, 5])
    def test_bf16_wire_bitwise_vs_allreduce(self, world):
        bf = np.dtype(jnp.bfloat16)
        rng = np.random.default_rng(10 + world)
        x = [rng.normal(size=10_007).astype(np.float32).astype(bf)
             for _ in range(world)]
        ar, _ = _run_ring(world, lambda c, ring, r: c._do_allreduce_wire(
            ring, [x[r].copy()], [np.dtype(np.float32)], "sum"))
        rs, _ = _run_ring(
            world, lambda c, ring, r: c._do_reduce_scatter_wire(
                ring, [x[r].copy()], [np.dtype(np.float32)], "sum"))
        full = np.concatenate([rs[r][0] for r in range(world)])
        np.testing.assert_array_equal(full, ar[0][0])

    def test_ring_byte_accounting(self):
        # Exact reduce-scatter = the ring's reduce-scatter phase + one
        # ownership-shift hop = 1.0*payload per rank, vs the allreduce's
        # 2(n-1)/n: equal at world 2, strictly fewer from world 3 on.
        # The wire path at world 2 exchanges only the peer's raw stripe:
        # half of allreduce_wire's full-buffer hop.
        x = np.ones(99_999, np.float32)
        for world in (2, 3):
            _, ar = _run_ring(
                world, lambda c, ring, r: c._do_allreduce_wire(
                    ring, [x.copy()], [np.dtype(np.float32)], "sum"))
            _, rs = _run_ring(
                world, lambda c, ring, r: c._do_reduce_scatter_wire(
                    ring, [x.copy()], [np.dtype(np.float32)], "sum"))
            assert abs(rs[0].ring_bytes_total() - x.nbytes) < 64
            want = 2 * (world - 1) / world * x.nbytes
            assert abs(ar[0].ring_bytes_total() - want) < 64
        bf = np.dtype(jnp.bfloat16)
        xb = x.astype(bf)
        _, arw = _run_ring(2, lambda c, ring, r: c._do_allreduce_wire(
            ring, [xb.copy()], [np.dtype(np.float32)], "sum"))
        _, rsw = _run_ring(
            2, lambda c, ring, r: c._do_reduce_scatter_wire(
                ring, [xb.copy()], [np.dtype(np.float32)], "sum"))
        assert abs(rsw[0].ring_bytes_total()
                   - arw[0].ring_bytes_total() / 2) < 4

    def test_mean_op_divides_stripe(self):
        x = np.full(1000, 3.0, np.float32)
        rs, _ = _run_ring(2, lambda c, ring, r: c._do_reduce_scatter_wire(
            ring, [x.copy()], [np.dtype(np.float32)], "mean"))
        np.testing.assert_array_equal(
            np.concatenate([rs[0][0], rs[1][0]]), np.full(1000, 3.0))


# ------------------------------------------------- wrapper contracts

class _RecordingComm(Communicator):
    """Fake inner comm recording reduce_scatter_wire forwarding."""

    def __init__(self, world=2, rank=0, fail=False):
        self._world, self._rank = world, rank
        self._fail = fail
        self.calls = []

    def configure(self, store_addr, rank, world_size):
        pass

    def allreduce(self, tree, op="sum"):
        from torchft_tpu.manager import _instant
        return _instant(tree)

    def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
        raise AssertionError(
            "wrapper fell back to allreduce_wire instead of forwarding")

    def reduce_scatter_wire(self, buffers, orig_dtypes, op="sum"):
        from torchft_tpu.manager import _instant
        self.calls.append(("rs", len(list(buffers)), op))
        if self._fail:
            raise RuntimeError("boom")
        return _instant(_slice_shards(
            [np.ravel(np.asarray(b)).astype(d)
             for b, d in zip(buffers, orig_dtypes)],
            self._rank, self._world))

    def broadcast(self, tree, root=0):
        from torchft_tpu.manager import _instant
        return _instant(tree)

    def allgather(self, tree):
        from torchft_tpu.manager import _instant
        return _instant([tree] * self._world)

    def barrier(self):
        from torchft_tpu.manager import _instant
        return _instant(None)

    def rank(self):
        return self._rank

    def size(self):
        return self._world

    def shutdown(self):
        pass


class TestWrapperContracts:
    def test_default_impl_slices_allreduce_wire(self):
        # The ABC default must produce exactly this rank's stripe of the
        # allreduce_wire result — correctness floor for any backend that
        # has not specialized reduce_scatter_wire.
        class Base(_RecordingComm):
            def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
                from torchft_tpu.manager import _instant
                return _instant([
                    np.ravel(np.asarray(b)).astype(d) * self._world
                    for b, d in zip(buffers, orig_dtypes)])

            reduce_scatter_wire = Communicator.reduce_scatter_wire

        c = Base(world=2, rank=1)
        out = c.reduce_scatter_wire(
            [np.arange(10, dtype=np.float32)], ["float32"]).result()
        b = shard_bounds(10, 2)
        np.testing.assert_array_equal(
            out[0], np.arange(10, dtype=np.float32)[b[1]:b[2]] * 2)

    def test_error_swallowing_forwards_and_latches(self):
        from torchft_tpu.communicator import ErrorSwallowingCommunicator

        inner = _RecordingComm(world=2, rank=1)
        c = ErrorSwallowingCommunicator(inner)
        out = c.reduce_scatter_wire(
            [np.ones(10, np.float32)], ["float32"]).result()
        assert inner.calls == [("rs", 1, "sum")]
        assert out[0].size == 5
        # A raising inner call latches and falls back to the stripe-
        # shaped structure-only default.
        inner2 = _RecordingComm(world=2, rank=1, fail=True)
        c2 = ErrorSwallowingCommunicator(inner2)
        out = c2.reduce_scatter_wire(
            [np.ones(10, np.float32)], ["float32"]).result()
        assert c2.error() is not None
        assert out[0].size == 5  # stripe geometry survives the error

    def test_managed_forwards_with_inner_geometry(self):
        from torchft_tpu.communicator import ManagedCommunicator

        inner = _RecordingComm(world=2, rank=1)
        mgr = MagicMock()
        mgr.errored.return_value = None
        mgr._comm = inner  # ManagedCommunicator reads the manager's comm
        c = ManagedCommunicator(mgr)
        out = c.reduce_scatter_wire(
            [np.ones(10, np.float32)], ["float32"]).result()
        assert inner.calls == [("rs", 1, "sum")]
        assert out[0].size == 5

    def test_chaos_forwards_on_own_stream(self):
        inner = _RecordingComm(world=2, rank=0)
        from torchft_tpu.chaos import ChaosCommunicator
        sched = ChaosSchedule(seed=1, endpoints={})
        c = ChaosCommunicator(inner, sched)
        c.reduce_scatter_wire(
            [np.ones(4, np.float32)], ["float32"]).result()
        assert inner.calls == [("rs", 1, "sum")]


# ----------------------------------------- Manager reduce_scatter

def _run_managers(world, body, mkw=None, heal_ranks=(),
                  echo_vote=False):
    """World thread-ranks, wired rings, mocked control plane; ``body``
    runs per rank with its Manager and returns that rank's result."""
    rings = _make_test_rings(world)
    out = [None] * world
    errors = []

    def run(rank):
        client = MagicMock()
        heal = rank in heal_ranks
        client.quorum.return_value = quorum_result(
            max_rank=(None if heal else rank),
            max_world_size=world - len(heal_ranks),
            replica_rank=rank, replica_world_size=world, heal=heal)
        if echo_vote:
            client.should_commit.side_effect = \
                lambda **kw: kw["should_commit"]
        else:
            client.should_commit.return_value = True
        m = make_manager(client, comm=_wired_comm(rings[rank], rank, world),
                         min_replica_size=world - len(heal_ranks),
                         **(mkw or {}))
        try:
            out[rank] = body(m, rank)
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            errors.append(e)
        finally:
            m.shutdown()

    state = {"user": {}, "torchft": {"step": 1, "batches_committed": 0}}
    cp = patch("torchft_tpu.manager.CheckpointServer.load_from_address",
               return_value=state)
    pc = patch("torchft_tpu.manager.ManagerClient")
    with cp, pc:
        ts = [threading.Thread(target=run, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        alive = [t for t in ts if t.is_alive()]
    for r in rings:
        r.close()
    assert not alive, "manager rig deadlocked"
    assert not errors, errors
    return out


GRADS = {
    "a": np.random.default_rng(0).normal(size=(257, 3)).astype(np.float32),
    "b": np.random.default_rng(1).normal(size=(1000,)).astype(np.float32),
}


class TestManagerReduceScatter:
    @pytest.mark.parametrize("wire", [None, "bf16"])
    def test_stripes_concat_to_allreduce_result(self, wire):
        mkw = {"allreduce_bucket_bytes": 1024}
        if wire == "bf16":
            mkw["allreduce_wire_dtype"] = jnp.bfloat16

        def tf(rank):
            return jax.tree_util.tree_map(
                lambda a: jnp.asarray(a) * (rank + 1), GRADS)

        def ar_body(m, rank):
            m.step()
            got = m.allreduce(tf(rank)).result(timeout=30)
            assert m.errored() is None, m.errored()
            return jax.tree_util.tree_map(np.asarray, got)

        def rs_body(m, rank):
            m.step()
            sg = m.reduce_scatter(tf(rank)).result(timeout=30)
            assert m.errored() is None, m.errored()
            assert isinstance(sg, ShardedGrads)
            assert m.metrics()["reduce_scatter_count"] == 1
            return sg

        ar = _run_managers(2, ar_body, mkw)
        rs = _run_managers(2, rs_body, mkw)
        # Reassemble the flat chunks from both ranks' stripes and
        # compare to the allreduce leaves, chunk by chunk.
        leaves_ar = jax.tree_util.tree_leaves(ar[0])
        for k, c in enumerate(rs[0].chunks):
            full = np.concatenate([np.asarray(rs[r].shards[k])
                                   for r in range(2)])
            want = np.concatenate([
                np.ravel(np.asarray(leaves_ar[i])) for i in c.idx])
            np.testing.assert_array_equal(full, want)

    def test_healer_gets_zero_contribution_stripe(self):
        # Rank 1 heals: contributes zeros but still receives its stripe
        # of the participants' average — the same flow the allreduce
        # path guarantees, striped.
        def body(m, rank):
            m.step()
            g = {"g": jnp.asarray(GRADS["b"])} if rank == 0 else \
                {"g": jnp.zeros_like(jnp.asarray(GRADS["b"]))}
            sg = m.reduce_scatter(g).result(timeout=30)
            assert m.errored() is None, m.errored()
            return sg

        out = _run_managers(2, body, heal_ranks=(1,))
        full = np.concatenate([np.asarray(out[r].shards[0])
                               for r in range(2)])
        # Participant world is 1: rank 0's grads unscaled, on BOTH.
        np.testing.assert_array_equal(full, GRADS["b"])

    def test_latched_error_drops_update_bitwise(self):
        """Ring death mid reduce-scatter: the error latches, the future
        resolves to the zero-stripe structural default, the vote aborts,
        and the holder's params (and stripe optimizer state) are
        UNTOUCHED — the sync path's drop semantics."""
        def body(m, rank):
            m.step()
            m.wait_quorum()
            # Kill the ring under the collective: both ranks' sockets
            # die, the comm worker raises, wrap_future swallows.
            for ring in m._comm._rings:
                ring.close()
            tx = optax.adam(1e-2)
            opt = FTOptimizer(m, tx, jit=False)
            h = _Holder(jax.tree_util.tree_map(jnp.asarray, GRADS))
            p0 = jax.tree_util.tree_map(np.asarray, h.params)
            sg = m.reduce_scatter(
                jax.tree_util.tree_map(jnp.asarray, GRADS)).result(
                    timeout=30)
            assert m.errored() is not None
            assert isinstance(sg, ShardedGrads)  # geometry survives
            assert all(not np.any(np.asarray(s)) for s in sg.shards)
            committed = opt.apply(h, sg)
            assert committed is False
            for k in GRADS:
                np.testing.assert_array_equal(
                    np.asarray(h.params[k]), p0[k])
            assert opt._shard_state is None  # no stripe state committed
            assert m.metrics()["aborted_steps"] == 1
            return True

        out = _run_managers(
            2, body, {"shard_update": True}, echo_vote=True)
        assert out == [True, True]


# ------------------------------------------------ optimizer E2E

class TestShardedOptimizerE2E:
    """Full loop: reduce_scatter -> stripe adam update -> allgather ->
    reassemble, bitwise vs the sync allreduce+full-update path."""

    P0 = {"w": np.random.default_rng(7).normal(size=(37, 5)).astype(
        np.float32),
        "b": np.random.default_rng(8).normal(size=(113,)).astype(
            np.float32)}

    def _train(self, world, shard, steps, wire=None):
        rng = np.random.default_rng(42)
        grads = [[{k: rng.normal(size=v.shape).astype(np.float32)
                   for k, v in self.P0.items()}
                  for _ in range(world)] for _ in range(steps)]

        def body(m, rank):
            tx = optax.adam(1e-2)
            opt = FTOptimizer(m, tx, jit=False)
            h = _Holder(jax.tree_util.tree_map(jnp.asarray, self.P0),
                        None if shard else tx.init(self.P0))
            for s in range(steps):
                m.step()
                g = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a) * (rank + 1), grads[s][rank])
                fut = (m.reduce_scatter(g) if shard else m.allreduce(g))
                assert opt.apply(h, fut.result(timeout=30))
                assert m.errored() is None, m.errored()
            return {"params": jax.tree_util.tree_map(np.asarray, h.params),
                    "state_bytes": opt.shard_state_bytes(),
                    "metrics": m.metrics()}

        mkw = {"allreduce_bucket_bytes": 512, "shard_update": shard}
        if wire is not None:
            mkw["allreduce_wire_dtype"] = wire
        return _run_managers(world, body, mkw)

    @pytest.mark.parametrize("wire", [None, jnp.bfloat16])
    def test_bitwise_vs_sync_path(self, wire):
        sync = self._train(2, False, 3, wire)
        shard = self._train(2, True, 3, wire)
        for r in range(2):
            for k in self.P0:
                np.testing.assert_array_equal(
                    sync[0]["params"][k], shard[r]["params"][k])

    def test_stripe_state_is_half_at_world2(self):
        shard = self._train(2, True, 2)
        full_bytes = sum(
            2 * v.nbytes for v in self.P0.values())  # adam mu+nu
        for r in range(2):
            got = shard[r]["state_bytes"]
            assert 0 < got < 0.62 * full_bytes, (got, full_bytes)
            assert shard[r]["metrics"]["update_count"] == 2
            assert shard[r]["metrics"]["update_ms_total"] > 0
            assert shard[r]["metrics"]["shard_state_bytes"] == got

    def test_plain_tree_in_shard_mode_uses_stripe_state(self):
        # Single-group fast paths hand apply() a plain averaged tree;
        # the world-1 stripe spelling must keep the SAME state store so
        # alternating paths never fork optimizer state.
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            max_world_size=1, replica_world_size=1)
        client.should_commit.return_value = True
        m = make_manager(client, min_replica_size=1,
                         shard_update=True)
        try:
            with patch("torchft_tpu.manager.ManagerClient"):
                tx = optax.sgd(0.1)
                opt = FTOptimizer(m, tx, jit=False)
                h = _Holder(jax.tree_util.tree_map(jnp.asarray, self.P0))
                m.step()
                g = jax.tree_util.tree_map(jnp.asarray, self.P0)
                assert opt.apply(h, m.allreduce(g).result(timeout=30))
                # sgd: p - 0.1*g with g == p
                np.testing.assert_allclose(
                    np.asarray(h.params["b"]), 0.9 * self.P0["b"],
                    rtol=1e-6)
                assert opt._shard_state is not None
        finally:
            m.shutdown()


# ------------------------------------------------- striped heal

def _serve(state, n):
    servers = [CheckpointServer(lambda: state, bind_host="127.0.0.1")
               for _ in range(n)]
    for s in servers:
        s.allow_checkpoint(1)
    return servers


HEAL_STATE = {f"l{i}": np.random.default_rng(50 + i)
              .normal(size=16_384).astype(np.float32) for i in range(12)}


class TestStripedHeal:
    def test_three_donors_bitwise_and_all_used(self):
        servers = _serve(HEAL_STATE, 3)
        try:
            addrs = [s.address() for s in servers]
            stats = {}
            out = CheckpointServer.load_from_address(
                addrs[0], HEAL_STATE, device_put=False, stats=stats,
                donor_addrs=addrs, stripe_seed=3)
            for k, arr in HEAL_STATE.items():
                assert np.asarray(out[k]).tobytes() == arr.tobytes()
            assert stats["donors_used"] == 3.0, stats
            assert stats["attempts"] == 1.0
            assert stats["bytes_resumed"] == 0.0
        finally:
            for s in servers:
                s.shutdown()

    def test_dead_donor_reassigns_only_its_stripe(self):
        servers = _serve(HEAL_STATE, 2)
        try:
            addrs = [s.address() for s in servers]
            # A refused-dial donor in the set: its stripe reassigns to
            # the survivors; ONLY that stripe is re-fetched.
            dead = addrs[0].replace(
                f":{urllib.parse.urlparse(addrs[0]).port}", ":1")
            stats = {}
            out = CheckpointServer.load_from_address(
                addrs[0], HEAL_STATE, device_put=False, stats=stats,
                donor_addrs=[addrs[0], dead, addrs[1]], stripe_seed=0)
            for k, arr in HEAL_STATE.items():
                assert np.asarray(out[k]).tobytes() == arr.tobytes()
            assert stats["stripe_donor_deaths"] >= 1.0, stats
            assert 0 < stats["bytes_resumed"] < stats["payload_bytes"]
        finally:
            for s in servers:
                s.shutdown()

    def test_donor_killed_mid_stripe(self):
        """A donor that dies AFTER serving part of its stripe (chaos
        kill_after_bytes): committed leaves stay committed, only the
        dead donor's remaining stripe re-fetches, final state bitwise."""
        import urllib.parse
        import random as _random

        servers = _serve(HEAL_STATE, 3)
        try:
            addrs = [s.address() for s in servers]
            seed = 5
            # Replicate load_from_address's seed-shuffle to kill a NON-
            # manifest donor mid-stripe (the manifest donor dying is the
            # separate failover path, covered elsewhere).
            shuffled = list(dict.fromkeys(addrs))
            _random.Random(seed).shuffle(shuffled)
            victim = shuffled[1]
            netloc = urllib.parse.urlparse(victim).netloc
            payload = sum(a.nbytes for a in HEAL_STATE.values())
            sched = ChaosSchedule(seed=seed, endpoints={
                f"heal:{netloc}": EndpointChaos(
                    kill_after_bytes=payload // 8),
            })
            chaos.install(sched)
            try:
                stats = {}
                out = CheckpointServer.load_from_address(
                    addrs[0], HEAL_STATE, device_put=False, stats=stats,
                    donor_addrs=addrs, stripe_seed=seed,
                    stall_timeout_sec=10)
            finally:
                chaos.uninstall()
            for k, arr in HEAL_STATE.items():
                assert np.asarray(out[k]).tobytes() == arr.tobytes()
            assert stats["stripe_donor_deaths"] >= 1.0, stats
            assert stats["bytes_resumed"] < stats["payload_bytes"], stats
        finally:
            for s in servers:
                s.shutdown()

    def test_seed_shuffle_spreads_first_donor(self):
        """Concurrent healers must not all open their first stream
        against the same donor: across replica-id-derived seeds, the
        shuffled stripe[0] (the donor the manifest and first stripe ride)
        takes more than one value."""
        servers = _serve({"w": np.ones(64, np.float32)}, 3)
        try:
            addrs = [s.address() for s in servers]
            first = set()
            for i in range(8):
                seen = {}

                def capture(session, addr, *a, **kw):
                    seen["addr"] = addr
                    raise RuntimeError("probe only")

                with patch.object(CheckpointServer, "_run_heal_loop",
                                  side_effect=capture):
                    with pytest.raises(RuntimeError, match="probe"):
                        CheckpointServer.load_from_address(
                            addrs[0], {"w": np.ones(64, np.float32)},
                            device_put=False, donor_addrs=addrs,
                            stripe_seed=_stripe_seed(f"healer-{i}"))
                first.add(seen["addr"])
            assert len(first) > 1, first
        finally:
            for s in servers:
                s.shutdown()

    def test_wave_exception_not_blamed_on_survivors(self):
        """A zero-progress striped wave evicts the donor that actually
        died, then re-raises THAT donor's exception while ``addr`` still
        names a healthy survivor. The retry loop must re-stripe over the
        survivors — not evict/blame ``addr``, not burn a failover
        (regression: the handler used to attribute the wave's exception
        to the current manifest donor)."""
        servers = _serve(HEAL_STATE, 3)
        try:
            addrs = [s.address() for s in servers]
            real = CheckpointServer._fetch_striped.__func__
            calls = {"n": 0}

            def flaky(cls, session, stripe, *a, **kw):
                if calls["n"] == 0:
                    # First wave: donor stripe[1] "dies" with zero
                    # leaves landed — exactly what _fetch_striped does,
                    # including the already-handled tag on the raise.
                    calls["n"] += 1
                    dead = stripe.pop(1)
                    with session.lock:
                        session.stripe_deaths += 1
                    e = ConnectionRefusedError(f"[chaos] {dead} refused")
                    e._heal_striped_handled = True
                    raise e
                return real(cls, session, stripe, *a, **kw)

            resolver_calls = []

            def resolver(i):
                resolver_calls.append(i)
                return addrs[0]

            stats = {}
            with patch.object(CheckpointServer, "_fetch_striped",
                              classmethod(flaky)):
                out = CheckpointServer.load_from_address(
                    addrs[0], HEAL_STATE, device_put=False, stats=stats,
                    donor_addrs=addrs, stripe_seed=0, donors=resolver)
            for k, arr in HEAL_STATE.items():
                assert np.asarray(out[k]).tobytes() == arr.tobytes()
            # ONE death, counted once; the survivors kept striping — no
            # failover burned, the resolver never consulted.
            assert stats["stripe_donor_deaths"] == 1.0, stats
            assert stats["donor_failovers"] == 0.0, stats
            assert not resolver_calls
        finally:
            for s in servers:
                s.shutdown()

    def test_single_donor_set_falls_back_to_plain_fetch(self):
        servers = _serve(HEAL_STATE, 1)
        try:
            stats = {}
            out = CheckpointServer.load_from_address(
                servers[0].address(), HEAL_STATE, device_put=False,
                stats=stats, donor_addrs=[servers[0].address()],
                stripe_seed=1)
            for k, arr in HEAL_STATE.items():
                assert np.asarray(out[k]).tobytes() == arr.tobytes()
            assert stats["donors_used"] == 1.0
        finally:
            for s in servers:
                s.shutdown()

    def test_serve_window_shares_one_plan(self):
        """Donor-side fix: concurrent requests of one serve window share
        ONE cached PytreePlan (and its once-computed digests) —
        lock_streaming mode included, where each GET used to re-plan
        (and re-digest) the live tree. Manifests 404 in lock_streaming
        mode, so the cache is probed with concurrent full GETs."""
        state = {"w": np.arange(4096, dtype=np.float32)}
        calls = []
        import torchft_tpu.checkpointing as cpt
        real = cpt.plan_pytree

        def counting(tree):
            calls.append(1)
            return real(tree)

        server = CheckpointServer(lambda: state, lock_streaming=True,
                                  bind_host="127.0.0.1")
        try:
            with patch.object(cpt, "plan_pytree", side_effect=counting):
                server.allow_checkpoint(1)
                url = server.address()
                errs = []

                def get():
                    try:
                        with urllib.request.urlopen(url, timeout=10) as r:
                            r.read()
                    except Exception as e:  # noqa: BLE001
                        errs.append(e)

                ts = [threading.Thread(target=get) for _ in range(4)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=30)
                assert not errs, errs
                assert len(calls) == 1, f"planned {len(calls)} times"
        finally:
            server.shutdown()


# -------------------------------------------- sharded checkpoints

class TestShardedCheckpoint:
    STATE = {"w": np.arange(60_000, dtype=np.float32).reshape(60, 1000),
             "b": np.ones(7, np.float64), "step": 3}

    def _target(self):
        return {"w": np.zeros((60, 1000), np.float32),
                "b": np.zeros(7), "step": 0}

    def test_roundtrip_and_verify(self, tmp_path):
        from torchft_tpu import checkpoint_io as cio

        p = str(tmp_path / "ckpt_5")
        cio.save_sharded(p, self.STATE, {"step": 5,
                                         "batches_committed": 5},
                         shards=3)
        names = sorted(os.listdir(tmp_path))
        assert names == ["ckpt_5", "ckpt_5.shard0", "ckpt_5.shard1",
                         "ckpt_5.shard2"]
        head = cio.verify(p)
        assert head["format"] == cio.SET_FORMAT
        assert head["shard_count"] == 3
        assert cio.read_meta(p)["step"] == 5
        user, mgr = cio.load(p, self._target(), device_put=False)
        np.testing.assert_array_equal(user["w"], self.STATE["w"])
        np.testing.assert_array_equal(user["b"], self.STATE["b"])
        assert user["step"] == 3 and mgr["step"] == 5

    def test_one_shard_set_is_valid(self, tmp_path):
        from torchft_tpu import checkpoint_io as cio

        p = str(tmp_path / "ckpt_1")
        cio.save_sharded(p, self.STATE, {"step": 1,
                                         "batches_committed": 1},
                         shards=1)
        cio.verify(p)
        user, _ = cio.load(p, self._target(), device_put=False)
        np.testing.assert_array_equal(user["w"], self.STATE["w"])

    def test_missing_shard_condemns_set(self, tmp_path):
        from torchft_tpu import checkpoint_io as cio

        p = str(tmp_path / "ckpt_9")
        cio.save_sharded(p, self.STATE, {"step": 9,
                                         "batches_committed": 9},
                         shards=2)
        os.unlink(p + ".shard0")
        with pytest.raises(cio.CheckpointCorruptError,
                           match="missing shard"):
            cio.verify(p)
        assert cio.recover(str(tmp_path)) is None

    def test_corrupt_shard_falls_back_to_older_complete(self, tmp_path):
        from torchft_tpu import checkpoint_io as cio

        old = str(tmp_path / "ckpt_4")
        cio.save(old, self.STATE, {"step": 4, "batches_committed": 4})
        p = str(tmp_path / "ckpt_5")
        cio.save_sharded(p, self.STATE, {"step": 5,
                                         "batches_committed": 5},
                         shards=2)
        # Flip one byte deep in shard1's payload.
        with open(p + ".shard1", "r+b") as f:
            f.seek(-20, os.SEEK_END)
            b = f.read(1)
            f.seek(-20, os.SEEK_END)
            f.write(bytes([b[0] ^ 0xFF]))
        stats = {}
        got = cio.recover(str(tmp_path), stats=stats)
        assert got is not None and got.endswith("ckpt_4")
        assert stats["ckpt_recover_fallbacks"] >= 1
        # The condemned set's members went aside with its head.
        leftover = [n for n in os.listdir(tmp_path)
                    if n.startswith("ckpt_5")
                    and not n.endswith(".corrupt")]
        assert not leftover, leftover
        # Monolithic v2 still loads after the fallback.
        user, _ = cio.load(got, self._target(), device_put=False)
        np.testing.assert_array_equal(user["w"], self.STATE["w"])

    def test_stale_generation_shard_rejected(self, tmp_path):
        """A shard left over from an OLDER save under the same name must
        not satisfy a newer head: set_id binds shards to their save."""
        from torchft_tpu import checkpoint_io as cio

        p = str(tmp_path / "ckpt_7")
        cio.save_sharded(p, self.STATE, {"step": 7,
                                         "batches_committed": 7},
                         shards=2)
        old_shard = (tmp_path / "ckpt_7.shard0").read_bytes()
        cio.save_sharded(p, self.STATE, {"step": 7,
                                         "batches_committed": 7},
                         shards=2)
        (tmp_path / "ckpt_7.shard0").write_bytes(old_shard)
        with pytest.raises(cio.CheckpointCorruptError,
                           match="set_id mismatch"):
            cio.verify(p)

    def test_async_checkpointer_shards_and_prunes(self, tmp_path):
        from torchft_tpu import checkpoint_io as cio
        from torchft_tpu.checkpoint_io import AsyncCheckpointer

        w = AsyncCheckpointer(keep=1, shards=2)
        try:
            for step in (1, 2):
                w.save_async(str(tmp_path / f"ckpt_{step}"), self.STATE,
                             {"step": step, "batches_committed": step})
                w.wait()
        finally:
            w.shutdown()
        names = sorted(os.listdir(tmp_path))
        # keep=1 pruned step 1's head AND its stripe files.
        assert names == ["ckpt_2", "ckpt_2.shard0", "ckpt_2.shard1"], \
            names
        got = cio.recover(str(tmp_path))
        assert got is not None and got.endswith("ckpt_2")
        user, _ = cio.load(got, self._target(), device_put=False)
        np.testing.assert_array_equal(user["w"], self.STATE["w"])
