"""Pallas TPU kernels for the compute hot spots of the model zoo + the
PerFedS² aggregation inner loop.

  flash_attention   blockwise online-softmax attention (causal/SWA/GQA)
  decode_attention  single-token query vs (ring) KV cache — serving hot spot
  ssd_scan          Mamba-2 SSD chunk-local terms
  fused_adam        fused optimizer update (p, m, v in one pass)
  stale_aggregate   Eq. (8) masked stale-gradient aggregation

``ops.py`` exposes jit'd wrappers; ``ref.py`` holds the pure-jnp oracles
every kernel is tested against.  Interpret mode follows the platform
(``interpret_mode``): the Pallas interpreter off the TPU, the compiled
kernel on it.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    ``None`` (every normal caller) decides from the platform: interpreted
    everywhere but the TPU, never on it.  An explicit bool is for compiling
    a kernel for a described chip from a host whose backend is the CPU.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
