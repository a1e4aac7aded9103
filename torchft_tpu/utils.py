"""Small shared utilities."""

from __future__ import annotations

import os
import socket


def row_view(shape: tuple, itemsize: int, cap_bytes: int) -> tuple:
    """How a leaf too wide for one slice is cut: ``(lead, rows per
    slice)``. The leaf is viewed as ``(-1,) + shape[lead:]`` with as many
    trailing axes kept whole as fit ``cap_bytes``, and a slice is a run
    of rows of that view — merging leading axes and cutting the first
    one moves no data on the device, where a ravel of the whole leaf is
    a leaf-sized copy. Shared by the gradient exchange's slices and the
    fetch engine's."""
    cap = max(cap_bytes // itemsize, 1)
    lead, row = len(shape), 1
    while lead > 0 and row * shape[lead - 1] <= cap:
        lead -= 1
        row *= shape[lead]
    return lead, cap // row


def div_by_count(a, n):
    """Divide a reduced leaf by the participant count, dtype-aware.

    True-divide + cast back for inexact dtypes — via ``jnp.issubdtype``,
    because bfloat16 (ml_dtypes) is NOT ``np.inexact`` and would silently
    floor sub-1.0 gradients to zero under the integer branch — and
    floor-divide for integers. The single spelling of this rule; used by
    the manager's 1/n scaling (host and jitted device paths) and the mesh
    backend's mean reduction."""
    import jax.numpy as jnp

    if jnp.issubdtype(a.dtype, jnp.inexact):
        return (a / n).astype(a.dtype)
    return a // n


def force_cpu_devices(n: int) -> None:
    """Point JAX at an ``n``-device virtual CPU platform. Must run before
    the first backend use (``jax.devices()``, any array op): it only sets
    configuration, it does not rebuild a backend that already exists. Used
    by the test suite and the multi-chip dry run."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is left to it (jax
    reads the variable itself) and no directory is set in code. Otherwise
    the cache lives in ``.jax_cache/`` at the root of the checkout: the
    path is part of what a run must find again, so it is never made from
    a temporary directory, a pid or the time. Every program is cached,
    however quickly it compiled: a cold start on the chip pays for each."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def advertise_host() -> str:
    """Hostname peers should dial; falls back to loopback when the hostname
    doesn't resolve (single-host test topologies)."""
    host = socket.gethostname()
    try:
        socket.getaddrinfo(host, None)
        return host
    except OSError:
        return "127.0.0.1"
