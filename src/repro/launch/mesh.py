"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the caller controls
when devices are enumerated (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import — see launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Per-chip roofline constants for one accelerator."""
    name: str
    peak_flops_bf16: float     # FLOP/s
    hbm_bw: float              # B/s
    ici_bw_per_link: float     # B/s per link


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: TPU v5e: Google Cloud "TPU v5e" documentation (197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s ICI per chip over four links).
#: TPU v4: Google Cloud "TPU v4" documentation.
BACKEND_SPECS = {
    "TPU v5 lite": BackendSpec("TPU v5 lite", peak_flops_bf16=197e12,
                               hbm_bw=819e9, ici_bw_per_link=50e9),
    "TPU v4": BackendSpec("TPU v4", peak_flops_bf16=275e12,
                          hbm_bw=1228e9, ici_bw_per_link=100e9),
}


def backend_spec(device_kind: str | None = None) -> BackendSpec:
    """Peaks for ``device_kind``; ``None`` reads the first local device.
    A device that is not in :data:`BACKEND_SPECS` (the CPU among them) is
    an error: no peak is ever assumed."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return BACKEND_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}: known "
            f"kinds are {sorted(BACKEND_SPECS)}") from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small local runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
