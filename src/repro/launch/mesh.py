"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state; callers control when
devices are enumerated.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to build these meshes on CPU.

Target hardware model: TPU v5e pods — 256 chips/pod in a (16,16) ICI torus.
Single-pod mesh: (data=16, model=16).  Multi-pod: (pod=2, data=16, model=16)
where the ``pod`` axis crosses DCN and is used only for pure-DP (training)
or replica scale-out (serving).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh

# TPU v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link (~4 links usable/chip)
HBM_BYTES = 16 * 1024 ** 3        # 16 GiB


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: Optional[int] = None, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU engine runs)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes) -> Mesh:
    # the sharding strategies place arrays through NamedSharding specs and
    # let the partitioner propagate the rest: Auto axes, not the Explicit
    # default of newer ``jax.make_mesh``
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_chip_count(mesh: Mesh) -> int:
    return math.prod(mesh.shape.values())
