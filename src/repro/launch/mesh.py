"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state.  The dry-run sets ``--xla_force_host_platform_device_count=512``
before any jax import; smoke tests and benchmarks see the real single CPU
device.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto (sharding propagated by the
    compiler), over ``devices`` when given."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for tests (requires >= n_data*n_model devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))


# v5e hardware constants (roofline) — the single source of truth is the
# MachineFacts schema (repro/profiler/facts.py): a measured profile may
# override them, and these module names re-export the analytic defaults
# so unprofiled consumers see byte-identical values.  facts.py is pure
# data + stdlib, so this import still never touches jax device state.
from repro.profiler.facts import HBM_BW  # noqa: E402,F401  bytes/s per chip
from repro.profiler.facts import ICI_BW  # noqa: E402,F401  bytes/s per link
from repro.profiler.facts import \
    PEAK_FLOPS_BF16  # noqa: E402,F401  per chip
