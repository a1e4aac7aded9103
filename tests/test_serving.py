"""Serving-tier tests (docs/design/serving.md): the delta-publication
protocol (head / manifest / ranged bytes, generation eviction,
long-poll), the crc-verified atomic swap (torn-read guarantee under
``TORCHFT_CHAOS`` net faults, publisher restart, relay death), delta
minimality (byte counters: a subscriber at generation G reaching G+1
fetches only changed-digest leaves), the relay fan-out tree, staleness
bounds, Manager.publish commit coupling, and ranged-fetch connection
reuse. The seeded subscriber-churn soak rides ``scripts/test.sh serve``
nightly (markers ``serve`` + ``slow`` + ``nightly``).

The CDN-scale half (marker ``relay``, ``scripts/test.sh relay``):
the quantized delta wire (``tft-publish-delta-1`` doc/body routes,
1/4-byte minimality, bitwise reconstruction, per-leaf crc fallback,
verbatim relay adoption), the lock-striped ``_RelayTable`` battery,
registration beats + head-fetch steering (dead-hint cooldown, TTL
expiry, relay-death re-parenting), and the steered-delta churn soak
(``relay`` + ``slow`` + ``nightly``).

No native library needed: the tier is pure HTTP + numpy.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future
from unittest.mock import MagicMock

import numpy as np
import pytest

from mockplane import make_manager, quorum_result
from torchft_tpu import chaos as chaos_mod
from torchft_tpu.chaos import ChaosSchedule, EndpointChaos
from torchft_tpu.checkpointing import CheckpointServer, _ConnectionPool
from torchft_tpu.retry import RetryError, RetryPolicy
from torchft_tpu.serialization import manifest_delta
from torchft_tpu.serving import (
    DELTA_FORMAT,
    HEAD_FORMAT,
    PublicationServer,
    StaleWeightsError,
    WeightPublisher,
    WeightRelay,
    WeightSubscriber,
    _DeltaSet,
    _RelayTable,
    _serve_endpoint,
)

pytestmark = pytest.mark.serve

# Varied leaf sizes so delta byte accounting is unambiguous.
_SIZES = {"emb": 4000, "w1": 2500, "b1": 100, "w2": 1500, "b2": 50,
          "head": 800}


def make_state(fill=None, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for k, n in _SIZES.items():
        out[k] = (np.full(n, float(fill), np.float32) if fill is not None
                  else rng.normal(size=n).astype(np.float32))
    out["step"] = 0
    return out


def template():
    return {k: np.zeros(n, np.float32) for k, n in _SIZES.items()} \
        | {"step": 0}


def leaf_bytes(*names):
    return sum(_SIZES[n] * 4 for n in names)


def assert_bitwise(a, b):
    for k in _SIZES:
        assert a[k].tobytes() == b[k].tobytes(), f"leaf {k} differs"


def fast_policy():
    return RetryPolicy(max_attempts=4, base_delay_ms=5.0, jitter=0.0)


@pytest.fixture
def rig():
    pub = WeightPublisher(keep_generations=2)
    srv = PublicationServer(pub, bind_host="127.0.0.1")
    subs = []

    def make_sub(parents=None, **kw):
        kw.setdefault("retry_policy", fast_policy())
        kw.setdefault("stall_timeout_sec", 10.0)
        s = WeightSubscriber(parents or srv.address(), template(), **kw)
        subs.append(s)
        return s

    yield pub, srv, make_sub
    for s in subs:
        s.stop()
    srv.shutdown()


class TestPublicationProtocol:
    def test_head_404_before_first_publish(self, rig):
        pub, srv, _ = rig
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.address() + "/head", timeout=10)
        assert ei.value.code == 404

    def test_head_manifest_and_ranged_data(self, rig):
        pub, srv, _ = rig
        state = make_state(seed=3)
        gen = pub.publish(state, step=7)
        with urllib.request.urlopen(srv.address() + "/head",
                                    timeout=10) as r:
            head = json.loads(r.read())
        assert head["format"] == HEAD_FORMAT
        assert head["generation"] == gen
        assert head["step"] == 7
        assert head["boot"]
        with urllib.request.urlopen(
                f"{srv.address()}/{gen}/manifest", timeout=10) as r:
            mf = json.loads(r.read())
        arrs = [e for e in mf["leaves"] if e["kind"] == "array"]
        assert len(arrs) == len(_SIZES)
        assert all("crc32" in e for e in arrs)
        assert mf["generation"] == gen and mf["step"] == 7
        # ranged read of one leaf's exact bytes (leaves flatten in
        # sorted-key order — look "emb" up by name)
        e = next(e for e in arrs if e["key"] == "emb")
        a = mf["preamble_len"] + e["offset"]
        req = urllib.request.Request(
            f"{srv.address()}/{gen}",
            headers={"Range": f"bytes={a}-{a + e['nbytes'] - 1}"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 206
            body = r.read()
        assert body == state["emb"].tobytes()
        # unsatisfiable range
        req = urllib.request.Request(
            f"{srv.address()}/{gen}",
            headers={"Range": f"bytes={mf['total_len'] + 5}-"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 416
        assert ei.value.headers["Content-Range"] == \
            f"bytes */{mf['total_len']}"

    def test_generation_eviction(self, rig):
        pub, srv, _ = rig
        for g in range(1, 4):
            pub.publish(make_state(fill=g), step=g)
        # keep_generations=2: gen 1 is gone, 2 and 3 fetchable
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.address()}/1/manifest",
                                   timeout=10)
        assert ei.value.code == 404
        for g in (2, 3):
            with urllib.request.urlopen(f"{srv.address()}/{g}/manifest",
                                        timeout=10) as r:
                assert json.loads(r.read())["generation"] == g

    def test_long_poll_returns_on_publish(self, rig):
        pub, srv, make_sub = rig
        pub.publish(make_state(fill=1), step=1)
        sub = make_sub()
        assert sub.sync() is True
        threading.Timer(
            0.3, lambda: pub.publish(make_state(fill=2), step=2)).start()
        t0 = time.monotonic()
        assert sub.sync(wait_s=5.0) is True
        elapsed = time.monotonic() - t0
        assert elapsed < 4.0, "long-poll should return on publish, not " \
                              f"timeout (took {elapsed:.1f}s)"
        assert sub.generation() == 2

    def test_auth_token_gate(self):
        pub = WeightPublisher()
        srv = PublicationServer(pub, bind_host="127.0.0.1",
                                auth_token="sekrit")
        try:
            pub.publish(make_state(fill=1), step=1)
            bad = WeightSubscriber(srv.address(), template(),
                                   retry_policy=fast_policy())
            with pytest.raises(urllib.error.HTTPError) as ei:
                bad.sync()
            assert ei.value.code == 401
            good = WeightSubscriber(srv.address(), template(),
                                    auth_token="sekrit",
                                    retry_policy=fast_policy())
            assert good.sync() is True
            bad.stop()
            good.stop()
        finally:
            srv.shutdown()

    def test_manifest_delta_unit(self):
        pub = WeightPublisher()
        s1 = make_state(seed=1)
        pub.publish(s1, step=1)
        mf1 = pub._head.manifest
        s2 = dict(s1)
        s2["b1"] = s1["b1"] + 1
        pub.publish(s2, step=2)
        mf2 = pub._head.manifest
        d = manifest_delta(mf1, mf2)
        assert d["changed_bytes"] == leaf_bytes("b1")
        assert len(d["changed"]) == 1
        assert d["leaves"] == len(_SIZES)
        cold = manifest_delta(None, mf2)
        assert cold["changed_bytes"] == d["total_bytes"]


class TestDeltaFetch:
    def test_first_sync_is_full_then_delta_minimal(self, rig):
        pub, srv, make_sub = rig
        s1 = make_state(seed=5)
        pub.publish(s1, step=1)
        sub = make_sub()
        assert sub.sync() is True
        m = sub.metrics()
        # first sync fetches every leaf's body bytes
        assert m["serve_delta_bytes_last"] == leaf_bytes(*_SIZES)
        assert m["serve_leaves_carried_last"] == 0
        assert_bitwise(sub.weights(), s1)
        # small-touch update: two leaves change
        s2 = dict(s1)
        s2["b2"] = s1["b2"] * 2 + 1
        s2["head"] = s1["head"] + 0.5
        pub.publish(s2, step=2)
        assert sub.sync() is True
        m = sub.metrics()
        assert m["serve_delta_bytes_last"] == leaf_bytes("b2", "head")
        assert m["serve_leaves_fetched_last"] == 2
        assert m["serve_leaves_carried_last"] == len(_SIZES) - 2
        assert_bitwise(sub.weights(), s2)
        # publisher-side delta accounting agrees
        pm = pub.metrics()
        assert pm["publish_delta_bytes_last"] == leaf_bytes("b2", "head")
        assert pm["publish_changed_leaves_last"] == 2

    def test_identical_republish_costs_zero_bytes(self, rig):
        pub, srv, make_sub = rig
        s1 = make_state(seed=6)
        pub.publish(s1, step=1)
        sub = make_sub()
        sub.sync()
        pub.publish(dict(s1), step=2)  # nothing changed
        assert sub.sync() is True
        m = sub.metrics()
        assert m["serve_delta_bytes_last"] == 0
        assert m["serve_leaves_carried_last"] == len(_SIZES)
        assert sub.generation() == 2

    def test_skip_ahead_generations(self, rig):
        """A slow subscriber jumping G -> G+2 still fetches one delta
        (vs the newest), not the intermediate history."""
        pub, srv, make_sub = rig
        s1 = make_state(seed=7)
        pub.publish(s1, step=1)
        sub = make_sub()
        sub.sync()
        s2 = dict(s1)
        s2["b1"] = s1["b1"] + 1
        pub.publish(s2, step=2)
        s3 = dict(s2)
        s3["b1"] = s2["b1"] + 1
        pub.publish(s3, step=3)
        assert sub.sync() is True
        assert sub.generation() == 3
        assert sub.metrics()["serve_delta_bytes_last"] == leaf_bytes("b1")
        assert_bitwise(sub.weights(), s3)

    def test_device_put_subscriber(self, rig):
        import jax
        import jax.numpy as jnp

        pub, srv, _ = rig
        s1 = make_state(seed=8)
        pub.publish(s1, step=1)
        tmpl = {k: jnp.zeros(n, jnp.float32) for k, n in _SIZES.items()} \
            | {"step": 0}
        sub = WeightSubscriber(srv.address(), tmpl, device_put=True,
                               retry_policy=fast_policy())
        try:
            assert sub.sync() is True
            w = sub.weights()
            assert isinstance(w["emb"], jax.Array)
            assert np.asarray(w["emb"]).tobytes() == s1["emb"].tobytes()
        finally:
            sub.stop()


class TestTornReadGuarantee:
    """The acceptance invariant: under net chaos, publisher restart, and
    relay death mid-transfer, a subscriber NEVER observes a torn or
    uncommitted weight set — every visible tree is bitwise one of the
    published generations."""

    def _assert_uniform(self, tree, expected_gens):
        vals = {k: tree[k][0] for k in _SIZES}
        first = next(iter(vals.values()))
        assert all(v == first for v in vals.values()), \
            f"TORN TREE: mixed generation fills {vals}"
        for k in _SIZES:
            assert np.all(tree[k] == tree[k][0]), f"torn leaf {k}"
        assert int(first) in expected_gens, \
            f"unpublished fill {first} observed"

    def test_chaos_net_faults_never_tear(self, rig):
        pub, srv, make_sub = rig
        sched = ChaosSchedule(seed=1234, endpoints={
            "serve": EndpointChaos(reset_rate=0.10, short_rate=0.15),
        })
        chaos_mod.install(sched)
        try:
            sub = make_sub()
            published = set()
            for g in range(1, 6):
                pub.publish(make_state(fill=g), step=g)
                published.add(g)
                deadline = time.monotonic() + 60
                while sub.generation() < g:
                    try:
                        sub.sync()
                    except (RetryError, urllib.error.HTTPError,
                            ConnectionError, ValueError):
                        pass  # chaos round; held weights must stay sane
                    self._assert_uniform(sub.weights(), published) \
                        if sub.generation() else None
                    assert time.monotonic() < deadline, \
                        "sync never converged under chaos"
                self._assert_uniform(sub.weights(), {g})
            assert sched.fault_count() > 0, "chaos never fired — rig bug"
            assert_bitwise(sub.weights(), make_state(fill=5))
        finally:
            chaos_mod.uninstall()

    def test_parent_kill_mid_transfer_then_revive(self, rig):
        pub, srv, make_sub = rig
        s1 = make_state(fill=1)
        pub.publish(s1, step=1)
        sub = make_sub()
        sub.sync()
        ep = _serve_endpoint(srv.address())
        sched = ChaosSchedule(seed=7)
        chaos_mod.install(sched)
        try:
            sched.kill_endpoint(ep)
            pub.publish(make_state(fill=2), step=2)
            with pytest.raises((RetryError, ConnectionError)):
                sub.sync()
            # held weights unchanged and whole
            assert_bitwise(sub.weights(), s1)
            sched.revive_endpoint(ep)
            assert sub.sync() is True
            assert_bitwise(sub.weights(), make_state(fill=2))
        finally:
            chaos_mod.uninstall()

    def test_publisher_restart_new_boot(self):
        """A restarted publisher (fresh boot nonce, generation counter
        reset) must neither wedge nor tear the subscriber: the boot
        change forces a resync, digests carry unchanged leaves over."""
        pub1 = WeightPublisher()
        srv1 = PublicationServer(pub1, bind_host="127.0.0.1")
        port = int(srv1.address().rsplit(":", 1)[1].split("/")[0])
        s1 = make_state(seed=9)
        pub1.publish(s1, step=10)
        pub1.publish(s1, step=11)  # gen 2, same bytes
        sub = WeightSubscriber(srv1.address(), template(),
                               retry_policy=fast_policy())
        try:
            sub.sync()
            assert sub.generation() == 2
            srv1.shutdown()
            # "restart": fresh publisher process on the same port — new
            # boot, generation counter back at 1, one leaf changed.
            pub2 = WeightPublisher()
            s2 = dict(s1)
            s2["w2"] = s1["w2"] + 3
            srv2 = PublicationServer(pub2, bind_host="127.0.0.1",
                                     port=port)
            try:
                pub2.publish(s2, step=12)
                assert sub.sync() is True
                assert sub.generation() == 1  # new life's counter
                assert sub.step() == 12
                assert_bitwise(sub.weights(), s2)
                # digest carryover made the restart cheap: only the
                # changed leaf crossed the wire
                m = sub.metrics()
                assert m["serve_delta_bytes_last"] == leaf_bytes("w2")
            finally:
                srv2.shutdown()
        finally:
            sub.stop()


class TestBootTransitions:
    def test_no_flip_flop_between_stale_relay_and_restarted_root(self):
        """A wedged relay still serving the PREVIOUS publisher life next
        to a restarted root must not make the subscriber oscillate
        between lives: once a swap leaves boot A for boot B, boot A can
        never look 'fresher' again."""
        pub1 = WeightPublisher()
        srv1 = PublicationServer(pub1, bind_host="127.0.0.1")
        s_old = make_state(fill=1)
        pub1.publish(s_old, step=9)
        pub1.publish(s_old, step=9)  # gen 2 of boot A
        relay = WeightRelay(srv1.address(), template(),
                            bind_host="127.0.0.1",
                            retry_policy=fast_policy(), name="relayOld")
        relay.sync()  # holds boot A gen 2; its uplink now "wedges"
        # root restarts: new boot, counter back at 1, different state
        srv1.shutdown()
        pub2 = WeightPublisher()
        s_new = make_state(fill=2)
        srv2 = PublicationServer(pub2, bind_host="127.0.0.1")
        pub2.publish(s_new, step=3)
        sub = WeightSubscriber([relay.address(), srv2.address()],
                               template(), retry_policy=fast_policy())
        try:
            # converge onto the live life (may take one probe round)
            deadline = time.monotonic() + 20
            while True:
                sub.sync()
                if sub.weights()["emb"][0] == 2.0:
                    break
                assert time.monotonic() < deadline, "never left boot A"
            # ...and STAY there: the stale relay's old life must never
            # win again, no matter how many polls
            sub._last_probe = 0.0  # force the next probe window open
            for _ in range(6):
                assert sub.sync() is False
                assert sub.weights()["emb"][0] == 2.0
                assert_bitwise(sub.weights(), s_new)
        finally:
            sub.stop()
            relay.stop()
            srv2.shutdown()

    def test_cold_start_step_regression_resets_staleness(self):
        """A publisher cold-started from an old checkpoint legitimately
        REGRESSES steps (100 -> 60, new boot). Subscribers holding the
        newest generation in existence must not go dark on a staleness
        gauge still pinned at the dead life's step 100."""
        pub1 = WeightPublisher()
        srv1 = PublicationServer(pub1, bind_host="127.0.0.1")
        port = int(srv1.address().rsplit(":", 1)[1].split("/")[0])
        pub1.publish(make_state(fill=1), step=100)
        sub = WeightSubscriber(srv1.address(), template(),
                               retry_policy=fast_policy(),
                               max_lag_steps=5)
        try:
            sub.sync()
            assert sub.step() == 100
            srv1.shutdown()
            pub2 = WeightPublisher()
            srv2 = PublicationServer(pub2, bind_host="127.0.0.1",
                                     port=port)
            try:
                pub2.publish(make_state(fill=2), step=60)
                assert sub.sync() is True
                assert sub.step() == 60
                assert sub.lag_steps() == 0
                # the whole point: newest weights in existence stay
                # servable despite the apparent 40-step "lag"
                assert sub.weights()["emb"][0] == 2.0
            finally:
                srv2.shutdown()
        finally:
            sub.stop()


class TestRelayTree:
    def test_relay_serves_downstream_bitwise(self, rig):
        pub, srv, make_sub = rig
        s1 = make_state(seed=11)
        pub.publish(s1, step=1)
        relay = WeightRelay(srv.address(), template(),
                            bind_host="127.0.0.1",
                            retry_policy=fast_policy(), name="relayA")
        try:
            assert relay.sync() is True
            down = make_sub(parents=relay.address())
            assert down.sync() is True
            assert down.generation() == 1
            assert_bitwise(down.weights(), s1)
            # generation identity propagates: delta against the relay
            s2 = dict(s1)
            s2["b1"] = s1["b1"] - 1
            pub.publish(s2, step=2)
            relay.sync()
            down.sync()
            # only b1 moved; the relay's delta-mode publisher may serve
            # it as an exact-gated quantized wire (the -1 shift
            # reproduces bitwise), so the byte count is AT MOST the
            # changed leaf's f32 size — never the whole tree
            dm = down.metrics()
            assert 0 < dm["serve_delta_bytes_last"] <= leaf_bytes("b1")
            assert_bitwise(down.weights(), s2)
            rm = relay.metrics()
            assert rm["relay_publish_generations"] == 2
            assert rm["relay_serve_bytes_sent"] > 0
        finally:
            relay.stop()

    def test_stale_but_alive_relay_does_not_pin_subscriber(self, rig):
        """A relay whose own uplink wedged (alive, serving an old head)
        must not pin its subscribers: the already-current probe finds
        the fresher sibling parent, re-targets it, and the advertised
        head step still feeds the staleness gauge."""
        pub, srv, make_sub = rig
        s1 = make_state(seed=21)
        pub.publish(s1, step=1)
        relay = WeightRelay(srv.address(), template(),
                            bind_host="127.0.0.1",
                            retry_policy=fast_policy(), name="relayS")
        try:
            relay.sync()  # holds gen 1; never polls again (wedged)
            down = make_sub(parents=[relay.address(), srv.address()])
            down.sync()
            assert down.generation() == 1
            s2 = dict(s1)
            s2["w1"] = s1["w1"] + 1
            pub.publish(s2, step=2)  # relay never learns of gen 2
            assert down.sync() is True
            assert down.generation() == 2
            assert_bitwise(down.weights(), s2)
            assert down.metrics()["serve_delta_bytes_last"] == \
                leaf_bytes("w1")
        finally:
            relay.stop()

    def test_relay_death_fails_over_to_root(self, rig):
        """Relay dies mid-life: its subscriber rotates to the root
        publisher, resuming from committed (digest-matching) leaves —
        the delta stays a delta across the failover."""
        pub, srv, make_sub = rig
        s1 = make_state(seed=12)
        pub.publish(s1, step=1)
        relay = WeightRelay(srv.address(), template(),
                            bind_host="127.0.0.1",
                            retry_policy=fast_policy(), name="relayB")
        relay.sync()
        down = make_sub(parents=[relay.address(), srv.address()])
        down.sync()
        assert_bitwise(down.weights(), s1)
        relay.stop()  # relay process "dies"
        s2 = dict(s1)
        s2["head"] = s1["head"] * 0.5
        pub.publish(s2, step=2)
        assert down.sync() is True
        m = down.metrics()
        assert m["serve_parent_failovers"] >= 1
        assert m["serve_delta_bytes_last"] == leaf_bytes("head")
        assert m["serve_leaves_carried_last"] == len(_SIZES) - 1
        assert_bitwise(down.weights(), s2)


class TestStaleness:
    def test_max_lag_steps_bound(self, rig):
        pub, srv, make_sub = rig
        pub.publish(make_state(fill=1), step=10)
        # A "parent" that advertises step 15 but serves no data: the
        # subscriber learns how far behind it is, cannot close the gap.
        class HeadOnly(WeightPublisher):
            def handle_request(self, handler, send_timeout_sec=120.0):
                if handler.path.split("?")[0].rstrip("/") in (
                        "/publish", "/publish/head"):
                    self._send_json(handler, {
                        "format": HEAD_FORMAT, "generation": 99,
                        "step": 15, "boot": "elsewhere",
                        "total_len": 0, "manifest": "/publish/99/manifest",
                        "data": "/publish/99"}, send_timeout_sec)
                else:
                    handler.send_error(404, "no data here")

        fake_srv = PublicationServer(HeadOnly(), bind_host="127.0.0.1")
        try:
            sub = make_sub(parents=[srv.address()], max_lag_steps=3)
            sub.sync()
            assert sub.weights() is not None  # lag 0: fine
            # now the fleet's head moves to step 15 where we can't
            # follow (no data behind it): sync either rotates back to
            # the real parent and reports nothing new, or exhausts its
            # budget — either way the advertised step was LEARNED
            sub._parents.append(fake_srv.address())
            sub._parent_idx = 1
            try:
                sub.sync()
            except RetryError:
                pass
            assert sub.lag_steps() == 5
            with pytest.raises(StaleWeightsError):
                sub.weights()
            # a looser bound serves stale-but-bounded weights
            sub._max_lag_steps = 10
            assert sub.weights()["emb"][0] == 1.0
        finally:
            fake_srv.shutdown()

    def test_no_generation_yet_raises(self, rig):
        _, _, make_sub = rig
        sub = make_sub()
        with pytest.raises(StaleWeightsError):
            sub.weights()

    def test_background_thread_and_wait_generation(self, rig):
        pub, srv, make_sub = rig
        sub = make_sub(poll_interval_s=0.05)
        sub.start()
        pub.publish(make_state(fill=4), step=4)
        assert sub.wait_generation(1, timeout=20)
        assert_bitwise(sub.weights(), make_state(fill=4))
        pub.publish(make_state(fill=5), step=5)
        assert sub.wait_generation(2, timeout=20)
        sub.stop()


class TestConnectionReuse:
    def test_subscriber_reuses_connections(self, rig):
        pub, srv, make_sub = rig
        pub.publish(make_state(fill=1), step=1)
        sub = make_sub()
        sub.sync()
        pub.publish(make_state(fill=2), step=2)
        sub.sync()
        # 2 syncs = >= 4 requests (head+manifest+data each) over one
        # parent: everything after the first dial rides the kept-alive
        # connection.
        assert sub.metrics()["serve_redials_avoided"] >= 3

    def test_heal_fetch_reuses_connections(self):
        state = make_state(seed=13)
        server = CheckpointServer(lambda: state, bind_host="127.0.0.1")
        try:
            server.allow_checkpoint(1)
            stats = {}
            got = CheckpointServer.load_from_address(
                server.address(), template(), device_put=False,
                stats=stats)
            assert_bitwise(got, state)
            # manifest + body ride one connection: the second request
            # avoided a redial
            assert stats["redials_avoided"] >= 1
        finally:
            server.shutdown()

    def test_pool_survives_server_side_close(self):
        """A pooled connection the server idle-closed must transparently
        re-dial, not fail the request."""
        state = make_state(seed=14)
        pub = WeightPublisher()
        srv = PublicationServer(pub, bind_host="127.0.0.1",
                                send_timeout_sec=0.4)
        try:
            pub.publish(state, step=1)
            pool = _ConnectionPool()
            for i in range(2):
                resp = pool.request(f"{srv.address()}/head", 10.0, None)
                with resp:
                    assert json.loads(resp.read())["generation"] == 1
                time.sleep(0.8)  # server idle-closes the kept conn
            assert pool.redials >= 1
        finally:
            pool.close()
            srv.shutdown()


class TestManagerPublish:
    def _happy(self, state):
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        return make_manager(client, state_dict=lambda: state)

    def test_publish_and_subscribe_end_to_end(self):
        state = make_state(seed=15)
        m = self._happy(state)
        pub = WeightPublisher()
        sub = None
        try:
            m.step()
            assert m.should_commit()
            gen = m.publish(pub)
            assert gen == 1
            # served through the manager's own CheckpointServer — and
            # NOT step-gated: a closed heal window (commit in progress)
            # must not block publication fetches.
            m._ckpt_server.disallow_checkpoint()
            sub = WeightSubscriber(m.publish_address(), template(),
                                   retry_policy=fast_policy())
            assert sub.sync() is True
            assert_bitwise(sub.weights(), state)
            assert sub.step() == 1
            mx = m.metrics()
            assert mx["publish_count"] == 1
            assert mx["publish_last_generation"] == 1
            assert mx["publish_generations"] == 1
            assert "publish" in [e["event"] for e in m.history()]
        finally:
            if sub is not None:
                sub.stop()
            m.shutdown()

    def test_refuses_errored_aborted_healing_deferred(self):
        state = make_state(seed=16)
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = False  # vote aborts
        m = make_manager(client, state_dict=lambda: state)
        pub = WeightPublisher()
        try:
            m.step()
            m.report_error(RuntimeError("boom"))
            assert m.publish(pub) is None          # errored
            assert not m.should_commit()
            assert m.publish(pub) is None          # aborted
            with m._metrics_lock:
                m._healing = True
            assert m.publish(pub) is None          # mid-heal
            with m._metrics_lock:
                m._healing = False
            m._should_step = True
            m._errored = None
            fut = Future()
            m.stage_deferred(fut)
            assert m.publish(pub) is None          # deferred in flight
            fut.set_result(None)
            m.drain_deferred()
            mx = m.metrics()
            assert mx["publish_skipped"] == 4
            assert mx["publish_count"] == 0
            assert pub.head() is None  # nothing ever served
            skips = [e for e in m.history()
                     if e["event"] == "publish_skip"]
            assert len(skips) == 4
        finally:
            m.shutdown()


@pytest.mark.slow
@pytest.mark.nightly
class TestSubscriberChurnSoak:
    """Seeded churn soak: continuous publishing through a 2-relay tree
    while subscribers die/restart, one relay is killed mid-publish, and
    the serve channel injects resets/shorts — every visible tree must
    stay one of the published generations bitwise, and the fleet must
    converge on the final generation once the churn stops."""

    def test_churn_soak(self):
        sched = ChaosSchedule(seed=99, endpoints={
            "serve": EndpointChaos(reset_rate=0.04, short_rate=0.06),
        })
        chaos_mod.install(sched)
        pub = WeightPublisher(keep_generations=3)
        srv = PublicationServer(pub, bind_host="127.0.0.1")
        relays = [WeightRelay(srv.address(), template(),
                              bind_host="127.0.0.1",
                              retry_policy=fast_policy(),
                              poll_interval_s=0.05,
                              name=f"relay{i}").start()
                  for i in range(2)]
        subs = [WeightSubscriber(
                    [relays[i % 2].address(), srv.address()], template(),
                    retry_policy=fast_policy(), poll_interval_s=0.05,
                    name=f"sub{i}").start()
                for i in range(4)]
        published = set()
        torn: list = []

        def check(sub):
            try:
                tree = sub.weights()
            except StaleWeightsError:
                return
            vals = {k: tree[k][0] for k in _SIZES}
            first = next(iter(vals.values()))
            if not all(v == first for v in vals.values()) \
                    or int(first) not in published:
                torn.append((sub._name, vals))

        try:
            final_gen = 14
            for g in range(1, final_gen + 1):
                pub.publish(make_state(fill=g), step=g)
                published.add(g)
                for s in subs:
                    check(s)
                if g == 5:
                    # kill relay 0 mid-publish sequence: its subscribers
                    # must fail over to the root
                    sched.kill_endpoint(_serve_endpoint(
                        relays[0].address()))
                if g == 8:
                    # subscriber churn: one dies, a cold one joins
                    subs[0].stop()
                    subs[0] = WeightSubscriber(
                        [relays[1].address(), srv.address()], template(),
                        retry_policy=fast_policy(), poll_interval_s=0.05,
                        name="sub0b").start()
                if g == 10:
                    sched.revive_endpoint(_serve_endpoint(
                        relays[0].address()))
                time.sleep(0.25)
            # churn over: everyone must converge on the final state
            deadline = time.monotonic() + 90
            expected = make_state(fill=final_gen)
            for s in subs:
                while True:
                    check(s)
                    if s.generation() == final_gen:
                        break
                    assert time.monotonic() < deadline, \
                        f"{s._name} never converged " \
                        f"(at gen {s.generation()})"
                    time.sleep(0.1)
                assert_bitwise(s.weights(), expected)
            assert not torn, f"torn/unpublished trees observed: {torn}"
            assert sched.fault_count() > 0
        finally:
            chaos_mod.uninstall()
            for s in subs:
                s.stop()
            for r in relays:
                r.stop()
            srv.shutdown()


@pytest.mark.relay
class TestRelayTable:
    """The lock-striped beat table behind steering — unit battery."""

    def test_beat_rows_ttl_prune_and_age(self):
        t = _RelayTable(ttl_s=0.25)
        t.beat("r1", {"addr": "http://a/publish", "boot": "b",
                      "gen": 3, "children": 1})
        t.beat("r2", {"addr": "http://b/publish", "boot": "b",
                      "gen": 3, "children": 0})
        rows = t.rows()
        assert [r["id"] for r in rows] == ["r1", "r2"]
        assert all(r["age_s"] >= 0.0 for r in rows)
        assert t.count() == 2
        time.sleep(0.35)
        assert t.rows() == []  # TTL-pruned
        assert t.count() == 0

    def test_pick_least_loaded_fresh_same_boot(self):
        t = _RelayTable(ttl_s=10.0)
        t.beat("busy", {"addr": "http://busy", "boot": "b",
                        "gen": 5, "children": 7})
        t.beat("idle", {"addr": "http://idle", "boot": "b",
                        "gen": 5, "children": 1})
        t.beat("lagging", {"addr": "http://lag", "boot": "b",
                           "gen": 2, "children": 0})  # > 1 gen behind
        t.beat("otherlife", {"addr": "http://ob", "boot": "x",
                             "gen": 5, "children": 0})  # old boot
        assert t.pick("b", 5) == "http://idle"
        # nobody steerable: a fresh-boot head with an empty-enough table
        assert t.pick("nosuchboot", 5) is None

    def test_pick_spreads_between_beats_and_resets_on_beat(self):
        t = _RelayTable(ttl_s=10.0)
        t.beat("r1", {"addr": "http://r1", "boot": "b",
                      "gen": 1, "children": 0})
        t.beat("r2", {"addr": "http://r2", "boot": "b",
                      "gen": 1, "children": 0})
        # four steers between beats alternate instead of dog-piling
        got = sorted(t.pick("b", 1) for _ in range(4))
        assert got == ["http://r1", "http://r1",
                       "http://r2", "http://r2"]
        # a fresh beat resets r1's between-beat assignment counter, so
        # it immediately looks emptiest again
        t.beat("r1", {"addr": "http://r1", "boot": "b",
                      "gen": 1, "children": 0})
        assert t.pick("b", 1) == "http://r1"

    def test_pick_excludes_the_requesting_relay(self):
        t = _RelayTable(ttl_s=10.0)
        t.beat("only", {"addr": "http://only", "boot": "b",
                        "gen": 1, "children": 0})
        assert t.pick("b", 1, exclude_id="only") is None
        assert t.pick("b", 1) == "http://only"


@pytest.mark.relay
class TestQuantizedDeltaPublication:
    """The int8+pow2-scale delta wire (``tft-publish-delta-1``): doc
    format, 1/4-byte minimality, bitwise reconstruction, per-leaf crc
    fallback to the exact f32 route, and verbatim relay adoption."""

    def _rig(self, **kw):
        kw.setdefault("keep_generations", 2)
        pub = WeightPublisher(delta=True, **kw)
        srv = PublicationServer(pub, bind_host="127.0.0.1")
        return pub, srv

    def test_delta_doc_format_and_modes(self):
        pub, srv = self._rig()
        try:
            s1 = make_state(seed=31)
            pub.publish(s1, step=1)
            s2 = dict(s1)
            s2["b1"] = s1["b1"] + np.float32(1e-3)
            pub.publish(s2, step=2)
            with urllib.request.urlopen(
                    f"{srv.address()}/2/delta?base=1", timeout=10) as r:
                doc = json.loads(r.read())
            assert doc["format"] == DELTA_FORMAT
            assert doc["generation"] == 2 and doc["base"] == 1
            assert doc["boot"] == pub.head()["boot"]
            assert doc["body_len"] > 0
            modes = {e["key"]: e["mode"] for e in doc["leaves"]}
            assert modes["b1"] == "delta"
            assert all(m == "carry" for k, m in modes.items()
                       if k != "b1")
            ent = next(e for e in doc["leaves"] if e["key"] == "b1")
            for field in ("offset", "nbytes", "size", "seg_elems",
                          "wire_crc32", "base_crc32", "crc32"):
                assert field in ent, field
            # the delta leaf's crc32 IS the full manifest digest: both
            # routes describe the same bits
            mf = json.loads(urllib.request.urlopen(
                f"{srv.address()}/2/manifest", timeout=10).read())
            mf_ent = next(e for e in mf["leaves"] if e["key"] == "b1")
            assert ent["crc32"] == mf_ent["crc32"]
            # unknown base: the subscriber's full-route fallback signal
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{srv.address()}/2/delta?base=77", timeout=10)
            assert ei.value.code == 404
            # malformed: no base at all
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{srv.address()}/2/delta", timeout=10)
            assert ei.value.code == 400
        finally:
            srv.shutdown()

    def test_delta_sync_bitwise_and_quarter_bytes(self):
        pub, srv = self._rig()
        dsub = fsub = None
        try:
            rng = np.random.default_rng(32)
            s1 = make_state(seed=32)
            pub.publish(s1, step=1)
            dsub = WeightSubscriber(srv.address(), template(),
                                    retry_policy=fast_policy())
            fsub = WeightSubscriber(srv.address(), template(),
                                    retry_policy=fast_policy(),
                                    delta=False)
            dsub.sync()
            fsub.sync()
            s2 = dict(s1)
            s2["emb"] = (s1["emb"] + np.float32(1e-3)
                         * rng.normal(size=_SIZES["emb"])
                         .astype(np.float32))
            pub.publish(s2, step=2)
            assert dsub.sync() is True
            assert fsub.sync() is True
            dm = dsub.metrics()
            assert dm["serve_delta_syncs"] == 1
            assert dm["serve_delta_leaves_last"] == 1
            assert dm["serve_delta_crc_fallbacks"] == 0
            # wire minimality: int8 + pow2 scales ~ 1/4 of the changed
            # leaves' f32 bytes (publisher-side accounting agrees)
            pm = pub.metrics()
            assert pm["publish_delta_sets"] >= 1
            wire = pm["publish_delta_wire_bytes_last"]
            assert 0 < wire <= 0.27 * pm["publish_delta_bytes_last"]
            assert dm["serve_delta_wire_bytes_total"] == wire
            # reconstruction is BITWISE the published state: the delta
            # subscriber, the full subscriber, and the publisher's
            # retained reconstruction all hold the same bits
            dw, fw = dsub.weights(), fsub.weights()
            assert_bitwise(dw, fw)
            assert_bitwise(dw, pub._head.state)  # noqa: SLF001
        finally:
            for s in (dsub, fsub):
                if s is not None:
                    s.stop()
            srv.shutdown()

    def test_corrupt_delta_wire_falls_back_per_leaf_f32(self):
        """A corrupt wire payload must lose the LEAF, not the sync: the
        wire crc rejects it, the fallback counter ticks, and the leaf
        rides the exact-f32 full route — final bits identical."""
        pub, srv = self._rig()
        sub = None
        try:
            s1 = make_state(seed=33)
            pub.publish(s1, step=1)
            sub = WeightSubscriber(srv.address(), template(),
                                   retry_policy=fast_policy())
            sub.sync()
            s2 = dict(s1)
            s2["w1"] = s1["w1"] * np.float32(1.001)
            pub.publish(s2, step=2)
            # corrupt one byte of the stored delta body in place
            with pub._cond:  # noqa: SLF001 — fault injection
                rec = pub._gens[2]
            ds = pub._delta_set(rec, 1)  # noqa: SLF001
            bad = bytearray(ds.body)
            bad[len(bad) // 2] ^= 0xFF
            rec.deltas[1] = _DeltaSet(ds.doc, bytes(bad))
            assert sub.sync() is True
            m = sub.metrics()
            assert m["serve_delta_crc_fallbacks"] >= 1
            assert m["serve_delta_syncs"] == 0
            assert_bitwise(sub.weights(), pub._head.state)  # noqa: SLF001
        finally:
            if sub is not None:
                sub.stop()
            srv.shutdown()

    def test_missing_delta_set_falls_back_to_full_route(self):
        """A subscriber whose base generation fell out of the retained
        window gets a 404 on the delta route and converges via the full
        manifest/body path — delta is an optimization, never a
        dependency."""
        pub, srv = self._rig(keep_generations=2)
        sub = None
        try:
            s = make_state(seed=34)
            pub.publish(s, step=1)
            sub = WeightSubscriber(srv.address(), template(),
                                   retry_policy=fast_policy())
            sub.sync()
            for g in (2, 3):  # gen 1 (the sub's base) evicts at gen 3
                s = dict(s)
                s["b2"] = s["b2"] + np.float32(g)
                pub.publish(s, step=g)
            assert sub.sync() is True
            assert sub.generation() == 3
            m = sub.metrics()
            assert m["serve_delta_syncs"] == 0  # full route took it
            assert_bitwise(sub.weights(), pub._head.state)  # noqa: SLF001
        finally:
            if sub is not None:
                sub.stop()
            srv.shutdown()

    def test_relay_adopts_delta_verbatim(self):
        """The relay re-serves the root's wire payloads untouched, so a
        grandchild's delta reconstruction is bitwise the ROOT's
        reconstruction (re-encoding would drift: Int8Wire re-encode of
        a reconstruction is not idempotent)."""
        pub, srv = self._rig(keep_generations=3)
        relay = down = None
        try:
            rng = np.random.default_rng(35)
            s1 = make_state(seed=35)
            pub.publish(s1, step=1)
            relay = WeightRelay(srv.address(), template(),
                                bind_host="127.0.0.1",
                                retry_policy=fast_policy(),
                                register=False, name="deltarelay")
            relay.sync()
            down = WeightSubscriber(relay.address(), template(),
                                    retry_policy=fast_policy())
            down.sync()
            s2 = dict(s1)
            s2["head"] = (s1["head"] + np.float32(1e-3)
                          * rng.normal(size=_SIZES["head"])
                          .astype(np.float32))
            pub.publish(s2, step=2)
            assert relay.sync() is True
            assert relay.last_delta() is not None
            assert down.sync() is True
            dm = down.metrics()
            assert dm["serve_delta_syncs"] == 1
            assert dm["serve_delta_crc_fallbacks"] == 0
            # grandchild bits == root publisher's retained bits
            assert_bitwise(down.weights(), pub._head.state)  # noqa: SLF001
            rm = relay.metrics()
            assert rm["relay_serve_delta_requests"] >= 1
            assert rm["relay_serve_delta_bytes_sent"] > 0
        finally:
            for x in (down, relay):
                if x is not None:
                    x.stop()
            srv.shutdown()

    def test_delta_off_publisher_serves_no_delta_routes(self, rig):
        """Default publishers (delta off) never see delta requests: the
        subscriber only tries the delta route when the head advertises
        it."""
        pub, srv, make_sub = rig
        pub.publish(make_state(fill=1), step=1)
        sub = make_sub()  # delta=True default, but head says no
        sub.sync()
        pub.publish(make_state(fill=2), step=2)
        assert sub.sync() is True
        assert pub.metrics()["serve_delta_requests"] == 0
        assert sub.metrics()["serve_delta_syncs"] == 0


@pytest.mark.relay
class TestRelaySteering:
    """Relay registration beats, the ``/publish/relays`` surface, and
    head-fetch-time subscriber steering (live-relay hints, dead-hint
    cooldown, TTL expiry, death re-parenting)."""

    def test_beat_route_and_relays_endpoint(self, rig):
        pub, srv, _ = rig
        pub.publish(make_state(fill=1), step=1)
        boot = pub.head()["boot"]
        q = urllib.parse.urlencode(
            [("id", "r1"), ("addr", "http://x:1/publish"),
             ("boot", boot), ("gen", "1"), ("step", "1"),
             ("children", "2"), ("bytes_sent", "5")])
        with urllib.request.urlopen(
                f"{srv.address()}/relay/beat?{q}", timeout=10) as r:
            ack = json.loads(r.read())
        assert ack["ok"] is True and ack["relays"] == 1
        with urllib.request.urlopen(
                f"{srv.address()}/relays", timeout=10) as r:
            doc = json.loads(r.read())
        (row,) = doc["relays"]
        assert row["id"] == "r1" and row["lag_gens"] == 0
        assert row["age_s"] >= 0.0
        # malformed beat (no id) is a client error, not a crash
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{srv.address()}/relay/beat?addr=http://x:1", timeout=10)
        assert ei.value.code == 400
        m = pub.metrics()
        assert m["relay_beats"] == 1
        assert m["relays_live"] == 1
        assert m["relay_children_total"] == 2

    def test_subscriber_steered_to_live_relay(self, rig):
        pub, srv, make_sub = rig
        pub.publish(make_state(fill=1), step=1)
        relay = WeightRelay(srv.address(), template(),
                            bind_host="127.0.0.1",
                            retry_policy=fast_policy(),
                            beat_interval_s=0.1,
                            poll_interval_s=0.05,
                            name="steer-r1")
        try:
            relay.sync()
            relay.start()
            deadline = time.monotonic() + 5.0
            while not pub.relay_rows():
                assert time.monotonic() < deadline, "relay never beat in"
                time.sleep(0.02)
            sub = make_sub()
            assert sub.sync() is True
            # the head hint re-parented the sub onto the relay
            assert sub._parents[0] == relay.address()  # noqa: SLF001
            assert sub.metrics()["serve_steers"] >= 1
            assert pub.metrics()["relay_steers"] >= 1
            # the next generation flows through the relay, not the root
            pub.publish(make_state(fill=2), step=2)
            deadline = time.monotonic() + 5.0
            while relay.generation() < 2:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            assert sub.sync() is True
            assert sub.generation() == 2
            assert relay.metrics()["relay_serve_requests"] >= 1
            assert_bitwise(sub.weights(), make_state(fill=2))
        finally:
            relay.stop()

    def test_dead_hint_cools_down_and_root_serves(self, rig):
        """A hint pointing at a dead relay must cost one failover, not
        the sync: the subscriber rotates back to the root, remembers
        the bad address for the cooldown window, and converges."""
        pub, srv, make_sub = rig
        pub.publish(make_state(fill=1), step=1)
        # hand-beat a corpse into the table (port 1: refused fast)
        pub.relay_beat({"id": "corpse",
                        "addr": "http://127.0.0.1:1/publish",
                        "boot": pub.head()["boot"],
                        "gen": 1, "children": 0})
        sub = make_sub()
        assert sub.sync() is True
        m = sub.metrics()
        assert m["serve_steers"] >= 1
        assert m["serve_parent_failovers"] >= 1
        cur = sub._parents[sub._parent_idx  # noqa: SLF001
                           % len(sub._parents)]  # noqa: SLF001
        assert cur == srv.address().rstrip("/")
        assert "http://127.0.0.1:1/publish" in sub._steer_bad  # noqa: SLF001
        assert_bitwise(sub.weights(), make_state(fill=1))
        # still on cooldown: the next sync ignores the lingering row
        # (its TTL has not expired) instead of bouncing off it again
        pub.publish(make_state(fill=2), step=2)
        assert sub.sync() is True
        assert sub.metrics()["serve_parent_failovers"] == \
            m["serve_parent_failovers"]
        assert_bitwise(sub.weights(), make_state(fill=2))

    def test_registration_ttl_expires_dead_relay(self):
        pub = WeightPublisher(keep_generations=2, relay_ttl_s=0.3)
        srv = PublicationServer(pub, bind_host="127.0.0.1")
        relay = None
        try:
            pub.publish(make_state(fill=1), step=1)
            relay = WeightRelay(srv.address(), template(),
                                bind_host="127.0.0.1",
                                retry_policy=fast_policy(),
                                beat_interval_s=0.1,
                                poll_interval_s=0.05,
                                name="ttl-r1")
            relay.sync()
            relay.start()
            deadline = time.monotonic() + 5.0
            while not pub.relay_rows():
                assert time.monotonic() < deadline, "relay never beat in"
                time.sleep(0.02)
            assert relay.metrics()["relay_beats_sent"] >= 1
            relay.stop()
            relay = None
            time.sleep(0.5)  # > ttl with no beats
            assert pub.relay_rows() == []
            assert pub.metrics()["relays_live"] == 0
        finally:
            if relay is not None:
                relay.stop()
            srv.shutdown()

    def test_relay_death_mid_delta_reparents_subscriber(self):
        """Kill the relay a steered subscriber is attached to, mid
        delta stream: the sub's parent rotation walks it back to the
        root and the next delta generation lands bitwise — no torn
        observation, no stall."""
        pub = WeightPublisher(keep_generations=3, delta=True)
        srv = PublicationServer(pub, bind_host="127.0.0.1")
        relay = sub = None
        try:
            rng = np.random.default_rng(36)
            s1 = make_state(seed=36)
            pub.publish(s1, step=1)
            relay = WeightRelay(srv.address(), template(),
                                bind_host="127.0.0.1",
                                retry_policy=fast_policy(),
                                beat_interval_s=0.1,
                                poll_interval_s=0.05,
                                name="doomed-r1")
            relay.sync()
            relay.start()
            deadline = time.monotonic() + 5.0
            while not pub.relay_rows():
                assert time.monotonic() < deadline
                time.sleep(0.02)
            sub = WeightSubscriber(srv.address(), template(),
                                   retry_policy=fast_policy(),
                                   stall_timeout_sec=10.0)
            sub.sync()
            assert sub._parents[0] == relay.address()  # noqa: SLF001
            # one delta generation THROUGH the relay first
            s2 = dict(s1)
            s2["w2"] = (s1["w2"] + np.float32(1e-3)
                        * rng.normal(size=_SIZES["w2"])
                        .astype(np.float32))
            pub.publish(s2, step=2)
            deadline = time.monotonic() + 5.0
            while relay.generation() < 2:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            sub.sync()
            assert sub.metrics()["serve_delta_syncs"] >= 1
            # kill it; the table row ages out while the sub fails over
            relay.stop()
            relay = None
            s3 = dict(s2)
            s3["w2"] = (s2["w2"] + np.float32(1e-3)
                        * rng.normal(size=_SIZES["w2"])
                        .astype(np.float32))
            pub.publish(s3, step=3)
            assert sub.sync() is True
            assert sub.generation() == 3
            m = sub.metrics()
            assert m["serve_parent_failovers"] >= 1
            assert_bitwise(sub.weights(), pub._head.state)  # noqa: SLF001
        finally:
            if sub is not None:
                sub.stop()
            if relay is not None:
                relay.stop()
            srv.shutdown()

    def test_request_stop_unblocks_long_poll(self, rig):
        pub, srv, make_sub = rig
        pub.publish(make_state(fill=1), step=1)
        sub = make_sub(poll_interval_s=0.05)
        sub.sync()
        sub.start()
        time.sleep(0.2)  # loop is long-polling for gen 2
        t0 = time.monotonic()
        sub.request_stop()
        sub.stop()
        assert time.monotonic() - t0 < 3.0


@pytest.mark.relay
@pytest.mark.slow
@pytest.mark.nightly
class TestSteeredDeltaChurnSoak:
    """Nightly soak of the whole CDN stack at once: a delta-mode root,
    registered relays beating into the steering table, subscribers that
    arrive knowing only the root and get steered out, serve-channel
    chaos, a relay killed mid-stream (its table row keeps advertising
    it — steered subs must bounce off, cool down, and converge via the
    root), and subscriber churn. Uniform fill states shift every leaf
    by exactly 1.0 per generation, which the pow2-scale int8 wire
    quantizes EXACTLY, so the fill-uniformity torn check and the final
    bitwise oracle both stay valid under quantized deltas."""

    def test_steered_delta_churn_soak(self):
        sched = ChaosSchedule(seed=1907, endpoints={
            "serve": EndpointChaos(reset_rate=0.04, short_rate=0.06),
        })
        chaos_mod.install(sched)
        pub = WeightPublisher(keep_generations=3, delta=True,
                              relay_ttl_s=1.5)
        srv = PublicationServer(pub, bind_host="127.0.0.1")
        relays = [WeightRelay(srv.address(), template(),
                              bind_host="127.0.0.1",
                              retry_policy=fast_policy(),
                              poll_interval_s=0.05,
                              beat_interval_s=0.2,
                              name=f"steer-soak-relay{i}").start()
                  for i in range(2)]
        deadline = time.monotonic() + 10.0
        while len(pub.relay_rows()) < 2:
            assert time.monotonic() < deadline, "relays never registered"
            time.sleep(0.05)
        # every subscriber knows ONLY the root; steering spreads them
        subs = [WeightSubscriber(
                    srv.address(), template(),
                    retry_policy=fast_policy(), poll_interval_s=0.05,
                    name=f"steer-soak-sub{i}").start()
                for i in range(4)]
        published = set()
        torn: list = []

        def check(sub):
            try:
                tree = sub.weights()
            except StaleWeightsError:
                return
            vals = {k: tree[k][0] for k in _SIZES}
            first = next(iter(vals.values()))
            if not all(v == first for v in vals.values()) \
                    or int(first) not in published:
                torn.append((sub._name, vals))

        try:
            final_gen = 14
            for g in range(1, final_gen + 1):
                pub.publish(make_state(fill=g), step=g)
                published.add(g)
                for s in subs:
                    check(s)
                if g == 5:
                    # kill relay 0's serve plane mid-stream; its beats
                    # keep flowing, so the table still advertises it —
                    # steered subs must bounce off and cool down
                    sched.kill_endpoint(_serve_endpoint(
                        relays[0].address()))
                if g == 8:
                    subs[0].stop()
                    subs[0] = WeightSubscriber(
                        srv.address(), template(),
                        retry_policy=fast_policy(),
                        poll_interval_s=0.05,
                        name="steer-soak-sub0b").start()
                if g == 10:
                    sched.revive_endpoint(_serve_endpoint(
                        relays[0].address()))
                time.sleep(0.25)
            # the pow2 wire kept every generation exact
            assert_bitwise(pub._head.state,  # noqa: SLF001
                           make_state(fill=final_gen))
            deadline = time.monotonic() + 90
            expected = make_state(fill=final_gen)
            for s in subs:
                while True:
                    check(s)
                    if s.generation() == final_gen:
                        break
                    assert time.monotonic() < deadline, \
                        f"{s._name} never converged " \
                        f"(at gen {s.generation()})"
                    time.sleep(0.1)
                assert_bitwise(s.weights(), expected)
            assert not torn, f"torn/unpublished trees observed: {torn}"
            assert sched.fault_count() > 0
            # the stack actually exercised its new machinery
            assert pub.metrics()["relay_beats"] > 0
            assert pub.metrics()["relay_steers"] > 0
            assert sum(s.metrics()["serve_delta_syncs"]
                       for s in subs) > 0
        finally:
            chaos_mod.uninstall()
            for s in subs:
                s.stop()
            for r in relays:
                r.stop()
            srv.shutdown()
