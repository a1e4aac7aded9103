"""``chip_smoke.py`` rehearsed on the CPU: tiny widths, interpreted kernels.

The script's real run needs a TPU and is made through the builder's chip
tool. What can be checked here is everything but the chip: that each phase's
path and control flow work end to end (kernel check, one-group trainer, two
groups with a kill and a heal, and the four-device sharded phases on four
virtual devices), that a phase that raises ends the run non-zero, that a CPU
backend is refused without ``--rehearse``, and the shape of the last line.

Every run is a child process: the script must be the only thing in its
process that touches JAX, exactly as on the chip.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


def _run(args, env_extra=None, cwd=REPO, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = r.stdout.strip().splitlines()
    return r, lines


def _last(lines):
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    return last


def test_rehearsal_of_the_one_chip_run():
    """Phases 0-3 at tiny size; the cache goes to the checkout's own
    directory when JAX_COMPILATION_CACHE_DIR is unset."""
    r, lines = _run([str(SCRIPT), "--rehearse"])
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    out = r.stdout
    for phase in ("0 device", "1 kernel", "2 trainer", "3 fault tolerance"):
        assert f"[phase {phase}]" in out
    assert f"compile cache: {REPO / '.jax_cache'}" in out
    assert "REHEARSAL" in out
    assert "bitwise equal across the two groups" in out
    # a rehearsal never reports a TPU
    assert _last(lines) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_rehearsal_of_the_four_chip_phases(tmp_path):
    """--chips 4 on virtual devices runs only the sharded phases, each in a
    child of a process that never initialises a backend itself; with
    JAX_COMPILATION_CACHE_DIR set the cache is left to it."""
    cache = tmp_path / "cache"
    code = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main(['--rehearse', '--chips', '4'])\n"
        "from jax._src import xla_bridge\n"
        "sys.stderr.write(f'BACKENDS={len(xla_bridge._backends)}\\n')\n"
        "sys.exit(rc)\n")
    r, lines = _run(["-c", code], {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "BACKENDS=0" in r.stderr
    out = r.stdout
    assert "[phase a sharded group]" in out
    assert "[phase b sharded kill and heal]" in out
    assert "[phase 1 kernel]" not in out and "[phase 2 trainer]" not in out
    assert "losses agree within" in out
    assert "device: 4 x cpu" in out and out.count("device: 2 x cpu") == 2
    assert "bitwise equal across the two groups" in out
    assert f"compile cache: {cache}" in out
    assert any(cache.iterdir())
    assert _last(lines) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}


def test_a_part_that_fails_stops_its_peer_and_the_run():
    """(b)'s groups are processes: when one dies the other is stopped, not
    left waiting for it, and the run ends non-zero."""
    code = (
        "import sys, chip_smoke\n"
        "class Broken(chip_smoke.Part):\n"
        "    def __init__(self, args, part, extra, env):\n"
        "        if part == 'b1':\n"
        "            extra = [*extra, '--bogus']\n"
        "        super().__init__(args, part, extra, env)\n"
        "chip_smoke.Part = Broken\n"
        "sys.exit(chip_smoke.main(['--rehearse', '--chips', '4']))\n")
    r, lines = _run(["-c", code], timeout=300)
    assert r.returncode != 0
    assert "part b1 exited with 2" in r.stderr
    assert "FAILED in phase b sharded kill and heal" in r.stdout
    assert _last(lines) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}


def test_a_phase_that_raises_exits_non_zero():
    code = (
        "import sys, chip_smoke\n"
        "def boom(run):\n"
        "    raise RuntimeError('injected failure')\n"
        "chip_smoke.phase_kernel = boom\n"
        "sys.exit(chip_smoke.main(['--rehearse']))\n")
    r, lines = _run(["-c", code])
    assert r.returncode != 0
    assert "injected failure" in r.stderr
    assert "FAILED in phase 1 kernel" in r.stdout
    assert "[phase 2 trainer]" not in r.stdout
    assert _last(lines)["ok"] is False


def test_a_cpu_backend_is_refused_without_rehearse():
    r, lines = _run([str(SCRIPT)])
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
    assert _last(lines)["ok"] is False


def test_the_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to drive."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {"PYTHONPATH": ""}
    r, _ = _run(["chip_smoke.py", "--rehearse"], env, cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


@pytest.mark.parametrize("flag", ["--chips=2", "--bogus", "--part=a"])
def test_unknown_arguments_are_refused(flag):
    r, _ = _run([str(SCRIPT), flag])
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
