"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is unavailable in CI; all sharding/collective tests
run on XLA's host platform with 8 virtual devices, which exercises the same
mesh/collective code paths the TPU build uses (the multi-"node" one-host
trick, mirroring the reference's thread-based integration tests,
/root/reference/torchft/manager_integ_test.py:144-154).
"""

import os

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
    import pytest

    return pytest.mark.skipif(
        not native_available(),
        reason="native control-plane library unavailable "
               "(no C++ toolchain)")
