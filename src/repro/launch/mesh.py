"""Production mesh builders (TPU v5e pods; 256 chips/pod).

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py forces
512 host devices).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# the rounds are written for GSPMD propagation from the argument shardings,
# so mesh axes are Auto (jax.make_mesh defaults to Explicit axes)
_AUTO2, _AUTO3 = (AxisType.Auto,) * 2, (AxisType.Auto,) * 3


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16). Two pods: (pod=2, data=16, model=16).

    data carries FedALIGN clients (+FSDP for the largest archs); model is
    tensor/expert parallel; pod is additional client parallelism across the
    DCN/ICI boundary.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _AUTO3 if multi_pod else _AUTO2)


def make_host_mesh(model_parallel: int = 1, devices=None):
    """(data, model) mesh over ``devices`` (default: every local device),
    e.g. (data=4, model=1) on a four-chip v5e host: clients over data."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    if n % model_parallel:
        raise ValueError(f"{n} devices do not split into model_parallel="
                         f"{model_parallel}")
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), _AUTO2, devices=devices)
