"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  ``make_device_mesh`` spans whatever devices
are attached (one chip, a 2x2 host, a pod); the dry-run's fixed shapes are
a single pod, 16x16 = 256 chips (TPU v5e numbers), and multi-pod, 2 pods x
256 = 512 chips with a leading "pod" axis that carries pure data
parallelism across the inter-pod links.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The fixed pod shapes the dry-run compiles against (host devices)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def device_mesh_shape(n: int):
    """(data, model) for ``n`` devices: the model axis is the largest power
    of two not above sqrt(n) — 1 -> (1, 1), 4 -> (2, 2), 8 -> (4, 2),
    256 -> (16, 16), the production pod."""
    model = 1
    while (2 * model) ** 2 <= n and n % (2 * model) == 0:
        model *= 2
    return n // model, model


def make_device_mesh(devices=None):
    """A (data, model) mesh over the attached devices (``--full`` runs)."""
    devices = jax.devices() if devices is None else list(devices)
    return _make_mesh(device_mesh_shape(len(devices)), ("data", "model"),
                      devices=devices)


def make_test_mesh(devices: int = 8):
    """Small mesh for CPU integration tests (data x model)."""
    d = min(devices, len(jax.devices()))
    model = 2 if d % 2 == 0 else 1
    return _make_mesh((d // model, model), ("data", "model"))


# TPU v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
