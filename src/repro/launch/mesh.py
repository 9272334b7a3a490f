"""Production mesh construction.

A FUNCTION, not a module constant: importing this module must never touch jax
device state (the dry-run pins the device count via XLA_FLAGS before any jax call).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def pin_host_device_count(n: int) -> None:
    """Force the host platform to expose ``n`` devices (the launchers'
    ``--devices`` flag).  Rewrites XLA_FLAGS -- any pre-set device-count flag
    is dropped, the rest is kept -- and must run before the first jax backend
    initialization (importing this module is safe; creating an array is not).
    """
    import os
    import re
    prev = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                  os.environ.get("XLA_FLAGS", ""))
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{prev.strip()} {flag}".strip()


def make_data_mesh(n: int):
    """1-D ``n``-way data mesh over the process's first ``n`` devices -- the
    shape every ``--devices N`` launcher uses."""
    return jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (tests / examples): 1-D data mesh or a
    (data, model) grid when enough local devices exist."""
    n = len(jax.devices())
    if model > 1 and n % model == 0:
        return jax.make_mesh((n // model, model), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


# ------------------------------------------------------ hardware model (v5e-like)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # B/s per chip
ICI_BW = 50e9                   # B/s per link (intra-pod)
CHIPS_PER_POD = 256
HBM_PER_CHIP = 16 * 2 ** 30
