"""Production meshes.

Target: TPU v5e pods — 16×16 = 256 chips per pod; 2 pods = 512 chips.
Axes: ``data`` (FSDP + batch), ``model`` (tensor/expert parallel), and on
multi-pod, ``pod`` (pure data parallel across the DCN; the axis gradient
compression targets).

Functions, not module constants — importing this module never touches jax
device state (smoke tests must keep seeing 1 CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated): the
    sharded paths here pad, ``shard_map`` and slice under that contract,
    while ``make_mesh`` defaults to ``Explicit`` axes, which refuse to slice
    a sharded dimension to a size the axis does not divide."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1, n_pod: int = 0):
    """Small mesh over however many (host) devices exist — used by tests."""
    if n_pod:
        return _auto_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_data_mesh(n_data: int | None = None):
    """1-axis pure data-parallel mesh — what the RSNN execution backend
    shards its sample axis over (``ExecutionBackend(mesh=...)``).  Defaults
    to every visible device (8 virtual CPU devices under the CI lane's
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    n = n_data or len(jax.devices())
    return _auto_mesh((n,), ("data",))


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (~3 usable links per axis)
DCN_BW = 25e9                     # B/s per host-ish (cross-pod; coarse)
# Physical VMEM per core: compiling for a described v5e, Mosaic accepts
# 127 MiB of kernel scratch and refuses 129 MiB against this size.  A
# pipelined kernel gets a 16 MiB scoped slice of it unless its
# CompilerParams ask for more (the fused train kernel asks for all of it).
VMEM_BYTES = 128 * 2 ** 20
