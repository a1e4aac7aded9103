"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is unavailable in CI; all sharding/collective tests
run on XLA's host platform with 8 virtual devices, which exercises the same
mesh/collective code paths the TPU build uses (the multi-"node" one-host
trick, mirroring the reference's thread-based integration tests,
/root/reference/torchft/manager_integ_test.py:144-154).
"""

import contextlib
import faulthandler
import os
import signal
import sys

import pytest

# Ask for the CPU with 8 virtual devices before any test touches a JAX
# backend. The env vars are also set for the subprocesses tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from torchft_tpu.utils import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import threading  # noqa: E402
from collections import Counter  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

import jax  # noqa: E402

from torchft_tpu.communicator import DummyCommunicator  # noqa: E402
from torchft_tpu.exchange import GradExchange  # noqa: E402
from torchft_tpu.tracing import Tracer  # noqa: E402


_NATIVE_AVAILABLE = None


def native_available() -> bool:
    """Memoized probe for the C++ control-plane library (builds it on
    first call when a toolchain exists). Shared by every native-gated
    test module — keep the skip logic in one place."""
    global _NATIVE_AVAILABLE
    if _NATIVE_AVAILABLE is None:
        try:
            from torchft_tpu import _native

            _native.lib()
            _NATIVE_AVAILABLE = True
        except Exception:  # noqa: BLE001 — no toolchain / no prebuilt .so
            _NATIVE_AVAILABLE = False
    return _NATIVE_AVAILABLE


def requires_native():
    """Skipif marker for tests needing the native control plane."""
    return pytest.mark.skipif(
        not native_available(),
        reason="native control-plane library unavailable "
               "(no C++ toolchain)")


# Every test's own time limit. The longest honest tier-1 test takes
# about a minute beside five busy workers; a test that runs past this
# is waiting on something that will not come, and one such test must
# not cost the whole run its clock.
TEST_LIMIT_S = 150.0
_REFIRE_S = 5.0


# The stack dump's file: stderr as it was before pytest's capture took
# fd 2 (capture is suspended while plugins are configured), so the dump
# reaches the log even when the test never returns to be reported.
_REAL_STDERR = sys.__stderr__


def pytest_configure(config):
    global _REAL_STDERR
    _REAL_STDERR = os.fdopen(os.dup(sys.__stderr__.fileno()), "w")


@contextlib.contextmanager
def time_limit(seconds, name):
    """Fail the enclosed block, naming ``name``, once it has run for
    ``seconds``: SIGALRM raises pytest's failure on the main thread, and
    again every few seconds until the block has unwound, because a
    ``finally`` or ``__exit__`` on the way out may join the very threads
    that hang. Shortly before (at nine tenths of the limit: at the same
    instant the dump would race the unwinding test and show pytest's
    stack, not the test's) faulthandler dumps every thread's stack to
    stderr, so the log says where it hung. What it cannot do: Python
    runs a signal handler between bytecodes, so a main thread inside a
    native call is interrupted only when the call returns (the stack
    dump still comes on time). Main thread only; it suspends an
    enclosing limit's alarm and re-arms what was left of it on exit (the
    enclosing stack dump is not re-armed)."""

    def on_alarm(signum, frame):
        pytest.fail(f"{name} ran past its limit of {seconds:g} s",
                    pytrace=True)

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    faulthandler.dump_traceback_later(0.9 * seconds, file=_REAL_STDERR)
    outer_left, _ = signal.setitimer(signal.ITIMER_REAL, seconds,
                                     _REFIRE_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, old_handler)
        if outer_left:
            signal.setitimer(signal.ITIMER_REAL, outer_left, _REFIRE_S)


@pytest.fixture(autouse=True)
def _test_time_limit(request):
    with time_limit(TEST_LIMIT_S, request.node.nodeid):
        yield


class ExchangeRig:
    """One :class:`~torchft_tpu.exchange.GradExchange` with what a
    ``Manager`` would lend it (a tracer, a counter sink, a put thread,
    the gauge) and no ``Manager``, control plane or store: for tests of
    the schedule, pack, stage, wait, put or the exchange as a whole."""

    def __init__(self, comm=None, **kw):
        self.tracer = Tracer(steps=8, enabled=True)
        self.counters = Counter()
        self.gauge = []
        self._lock = threading.Lock()
        self.executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="allreduce_put")
        args = dict(bucket_bytes=4 << 20, wire_dtype=None, wire_rung=0,
                    device_quant=True)
        args.update(kw)
        self.x = GradExchange(
            comm if comm is not None else DummyCommunicator(),
            self.tracer, self.record, self.executor, self.gauge.append,
            **args)

    def record(self, **deltas):
        with self._lock:
            self.counters.update(deltas)

    def run(self, op, tree, facts):
        """One step of ``op`` ("allreduce" / "reduce_scatter"): the
        result and what a failed step would have resolved to."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        fut, default_fn = getattr(self.x, op)(facts, tree, leaves,
                                              treedef)
        return fut.result(timeout=60), default_fn

    def close(self):
        self.executor.shutdown(wait=True)


@pytest.fixture
def exchange_rig():
    """``exchange_rig(comm=None, **GradExchange keywords)`` builds an
    :class:`ExchangeRig`; every one is closed after the test."""
    rigs = []

    def make(comm=None, **kw):
        rigs.append(ExchangeRig(comm, **kw))
        return rigs[-1]

    yield make
    for r in rigs:
        r.close()
