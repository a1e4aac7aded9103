"""The tests' one mocked control plane.

A real :class:`~torchft_tpu.manager.Manager` whose native
``ManagerClient`` is a ``MagicMock`` (the reference's strategy,
/root/reference/torchft/manager_test.py), the quorum it is handed, and
a dict-backed stand-in for the quorum's store. Every test file builds
its Manager here, so that one default decides what a mocked quorum
advertises: ``store_address=""``, the value for which
``Manager._store_client`` answers ``None`` without dialling. Any other
address is dialled for the Manager's whole ``timeout_ms`` (60 s) on
every quorum round; a test that wants a store either injects a
:class:`FakeStore` (``make_manager(store=...)`` and
``quorum_result(store_address=FAKE_STORE_ADDR)``) or starts a real one
(``_native.Store`` / the lighthouse).
"""

import threading
from unittest.mock import MagicMock

import numpy as np

from torchft_tpu._native import QuorumResult
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager

# The address under which make_manager(store=...) injects its store;
# nothing listens there and nothing may dial it.
FAKE_STORE_ADDR = "fake:0"


def quorum_result(**fields):
    """A healthy two-group quorum at step 1, this group rank 0;
    ``fields`` override any :class:`QuorumResult` field."""
    q = dict(quorum_id=1, recover_manager_address="manager:1234",
             store_address="", max_step=1, max_rank=0, max_world_size=2,
             replica_rank=0, replica_world_size=2, heal=False)
    q.update(fields)
    return QuorumResult(**q)


def mock_client(quorum=None):
    """A ManagerClient mock: every round answers ``quorum`` (default
    :func:`quorum_result`) and every vote commits."""
    client = MagicMock()
    client.quorum.return_value = \
        quorum if quorum is not None else quorum_result()
    client.should_commit.return_value = True
    return client


class FakeStore:
    """Dict-backed stand-in for the native StoreClient."""

    def __init__(self):
        self.kv = {}
        self.lock = threading.Lock()

    def set(self, key, value):
        with self.lock:
            self.kv[key] = value if isinstance(value, bytes) \
                else str(value).encode()

    def get(self, key, timeout_ms=0):
        with self.lock:
            if key not in self.kv:
                raise KeyError(key)
            return self.kv[key]


def make_manager(client=None, comm=None, *, store=None, quorum=None,
                 **manager_kwargs):
    """A Manager on a mocked client and a :class:`DummyCommunicator`.
    Without ``client`` it gets ``mock_client(quorum)``. ``store`` is
    seated in the Manager's per-address store-client cache under
    :data:`FAKE_STORE_ADDR`, so a quorum advertising that address reads
    and writes ``store`` and never dials. ``manager_kwargs`` override
    the defaults below or pass any other Manager argument."""
    kw = dict(load_state_dict=MagicMock(),
              state_dict=lambda: {"w": np.ones(2)},
              min_replica_size=2, rank=0, world_size=1,
              replica_id="testgroup")
    kw.update(manager_kwargs)
    m = Manager(comm=comm if comm is not None else DummyCommunicator(),
                _manager_client=client if client is not None
                else mock_client(quorum), **kw)
    if store is not None:
        m._healset_store = (FAKE_STORE_ADDR, store)
    return m


def boundary(m, tree=None):
    """One scripted step/allreduce/vote boundary; returns the vote."""
    m.step()
    m.allreduce(tree if tree is not None
                else {"g": np.ones(4, np.float32)}).result()
    return m.should_commit()
