"""Production meshes (TPU v5e pods).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod
axis carries cross-pod data parallelism (gradient all-reduce over DCI);
data/model stay intra-pod on ICI.

`make_production_mesh` is a FUNCTION so importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — shared by roofline + kernels
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
HBM_BYTES = 16 * 1024 ** 3        # 16 GiB


def _mesh(shape, axes):
    """Auto axes: the sharding rules and `with_sharding_constraint` hints
    are written for the compiler's propagation (jax.make_mesh would
    otherwise build Explicit axes, which refuse those hints)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    return _mesh((n // model_axis, model_axis), ("data", "model"))


def data_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
