"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as <name>/<name>.py (pl.pallas_call + BlockSpec),
ops.py (jit'd wrapper, custom_vjp where trainable) and ref.py (pure-jnp
oracle).  On the CPU, tests sweep shapes/dtypes against the oracle in
interpret mode, and tests/test_tpu_compile.py compiles each kernel for a
described v5e chip at real widths; ``chip_smoke.py`` runs each one on the
chip against its oracle.
"""

from . import decode_attention, flash_attention, fused_preprocess, ssd_scan

__all__ = ["decode_attention", "flash_attention", "fused_preprocess",
           "ssd_scan"]
