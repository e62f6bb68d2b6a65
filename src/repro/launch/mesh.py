"""Production mesh construction + backend (XLA) flag setup.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import os
import warnings

import jax

# XLA knobs for the latency-hiding round pipeline: let the scheduler pull
# each graph offset's collective-permute-start above the previous offset's
# decode/probe compute (the trainer issues them up front behind
# optimization_barriers — see docs/consensus_engine.md "Round pipeline").
# Async collective conversion itself is default-on in this XLA vintage
# (the old --xla_gpu_enable_async_collectives flag no longer exists), so
# the tunables that matter are the scheduler + stream priority. Both parse
# on every backend (the registry is global), but only the GPU compiler
# reads them, so the launcher arms them only where a GPU plugin is
# installed (``gpu_plugin_installed``). XLA aborts the process at backend
# start on a flag it does not know: --xla_gpu_enable_pipelined_collectives
# is gone from the installed XLA and must not come back here.
ASYNC_COLLECTIVE_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def gpu_plugin_installed() -> bool:
    """True when a CUDA or ROCm PJRT plugin for jax is installed.

    Decided from installed distributions, so it can run before the first
    jax backend touch (when XLA_FLAGS must already be final).
    """
    from importlib import metadata
    for dist in metadata.distributions():
        name = (dist.metadata["Name"] or "").lower().replace("_", "-")
        if name.startswith(("jax-cuda", "jax-rocm")):
            return True
    return False


def backend_initialized() -> bool:
    """True once any jax backend client exists (XLA_FLAGS are locked in)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:                       # pragma: no cover - jax internals
        # conservative fallback: assume initialized so we never silently
        # set flags that can no longer take effect
        return True


def set_backend_flags(*, async_collectives: bool = True,
                      host_device_count: int | None = None,
                      extra: tuple[str, ...] = ()) -> str | None:
    """Arm XLA_FLAGS for the round pipeline BEFORE first jax touch.

    Appends to — never clobbers — a user-set ``XLA_FLAGS`` env var, and
    skips any flag the user already spelled (their value wins). After jax
    backend initialization the env var is parsed and locked, so this
    becomes a warn-and-return no-op instead of silently writing flags
    that do nothing. Returns the new ``XLA_FLAGS`` value, or None when
    nothing changed.

    ``host_device_count`` adds ``--xla_force_host_platform_device_count``
    (the dry-run's 512-fake-device knob — it depends on this running
    before any backend init, hence the ordering guard).
    """
    wanted = list(ASYNC_COLLECTIVE_FLAGS) if async_collectives else []
    if host_device_count is not None:
        wanted.append("--xla_force_host_platform_device_count="
                      f"{int(host_device_count)}")
    wanted.extend(extra)
    if not wanted:
        return None
    if backend_initialized():
        warnings.warn(
            "set_backend_flags() called after jax initialized a backend: "
            "XLA_FLAGS are already locked in — flags not applied. Call it "
            "before the first jax device/computation touch.",
            RuntimeWarning, stacklevel=2)
        return None
    current = os.environ.get("XLA_FLAGS", "")
    present = {f.split("=", 1)[0] for f in current.split() if f}
    add = [f for f in wanted if f.split("=", 1)[0] not in present]
    if not add:
        return current or None
    merged = (current + " " if current else "") + " ".join(add)
    os.environ["XLA_FLAGS"] = merged
    return merged


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) v5e mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Every local device as one consensus node: ("pod", "data", "model")
    of shape (devices, 1, 1). One chip gives J = 1; a four-chip host gives
    J = 4, one node per chip."""
    return make_mesh((len(jax.devices()), 1, 1), ("pod", "data", "model"))


def make_debug_mesh(*, multi_pod: bool = False):
    """Small mesh for CPU integration tests (8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s per link
