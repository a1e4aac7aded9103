"""Tests of the tests' own rig: the mocked control plane never dials
(``mockplane.py``) and every test has a time limit (``conftest.py``)."""

import threading
from unittest.mock import patch

import numpy as np
import pytest

import conftest
from mockplane import boundary, make_manager, quorum_result


def test_mocked_two_group_quorum_never_dials_a_store():
    """A mocked quorum names no store, so the healset advertisement of
    a two-group quorum must construct no StoreClient: each one would
    dial a name nothing listens on for the Manager's whole timeout_ms.
    (A patch that raises would prove nothing: _publish_healset swallows
    exceptions.)"""
    assert quorum_result().replica_world_size == 2
    with patch("torchft_tpu.manager.StoreClient") as store_client:
        m = make_manager()
        try:
            for _ in range(2):
                assert boundary(m, {"g": np.ones(2)})
        finally:
            m.shutdown()
    assert not store_client.called


def test_time_limit_fails_the_block_by_name(tmp_path, monkeypatch):
    # The stack dump comes at nine tenths of the limit and the alarm at the
    # limit: a limit of 3 s leaves 0.3 s between them, which a loaded xdist
    # worker's scheduler does not eat (at 0.2 s the 20 ms did get eaten, and
    # the alarm's unwinding cancelled the dump).
    with open(tmp_path / "dump", "w+") as dump:
        monkeypatch.setattr(conftest, "_REAL_STDERR", dump)
        with pytest.raises(pytest.fail.Exception,
                           match=r"some::test ran past its limit of 3 s"):
            with conftest.time_limit(3.0, "some::test"):
                threading.Event().wait(30)
        dump.seek(0)
        # the stack dump that precedes the failure says where it waited
        assert "test_time_limit_fails_the_block_by_name" in dump.read()
