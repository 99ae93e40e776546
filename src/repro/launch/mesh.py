"""Mesh construction and per-chip peaks.

Meshes are built by FUNCTIONS (not module constants) so importing never
touches jax device state — dryrun.py sets XLA_FLAGS before any jax init.
``make_local_mesh`` spans whatever devices the process sees: the chips of
a TPU host, or host CPU devices under tests.

Topology (TPU v5e): one pod = 16x16 = 256 chips, mesh axes (data, model);
multi-pod adds the leading "pod" axis over the DCI: (2, 16, 16) = 512 chips.
"batch"/"fsdp" logical axes map to ("pod", "data") so both the gradient
all-reduce hierarchy (fast ICI within a pod, slow DCI across) and ZeRO
param sharding scale with total chips.

Every mesh axis is Auto: sharding is propagated by GSPMD from the logical
rules in distributed/sharding.py, not carried in array types.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1,
                    devices: Optional[Sequence[jax.Device]] = None):
    """``devices`` (default: all local devices) as a (data, model) mesh."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if n % model_axis:
        raise ValueError(f"{n} devices do not split into model axis {model_axis}")
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"),
                      devices=devices)


# Published per-chip peaks, keyed by jax's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip = 4 links).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # bytes/s
        "ici_bw": 50e9,              # bytes/s per link
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
