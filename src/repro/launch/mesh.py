"""Production mesh construction (TPU v5e pods).

Single pod: 16 x 16 = 256 chips, axes (data, model).
Two pods:   2 x 16 x 16 = 512 chips, axes (pod, data, model); the pod axis
carries pure data parallelism for training and doubles as the DENSE
*ensemble* axis in the server loop (DESIGN.md §6).

The federation-scale analogue is ``make_client_mesh``: a
("clients", "data") mesh whose leading axis shards the grouped engine's
stacked client dim (fl/sharding.py owns the specs/placement vocabulary;
DESIGN.md §8).

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""
from __future__ import annotations

import jax
import numpy as np

# TPU v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link


def axis_types_kw(n_axes: int) -> dict:
    """``jax.make_mesh`` kwargs for an all-Auto mesh: shardings propagate
    through the program (GSPMD) instead of being explicit in types."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **axis_types_kw(len(axes)))


def make_host_mesh(model: int = 1):
    """A tiny mesh over whatever devices exist — for smoke tests."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         **axis_types_kw(2))


def make_client_mesh(*, data: int = 1, devices=None):
    """("clients", "data") mesh over the host's devices.

    The ``clients`` axis shards the leading client dim of every stacked
    pytree the grouped engine produces (params, momentum, batch plans —
    fl/sharding.py); ``data`` carries batch parallelism and defaults to 1
    because the DENSE server's synthetic batch is broadcast to every
    client anyway. Takes the leading ``(n // data) * data`` devices so a
    non-divisible device count degrades instead of failing.
    """
    devs = list(devices if devices is not None else jax.devices())
    data = max(1, min(int(data), len(devs)))
    clients = len(devs) // data
    grid = np.asarray(devs[:clients * data], dtype=object)
    return jax.sharding.Mesh(grid.reshape(clients, data),
                             ("clients", "data"))


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
