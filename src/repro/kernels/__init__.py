"""Pallas TPU kernels for the compute hot-spots the paper optimizes,
each with a pure-jnp oracle (ref.py) and a jit'd wrapper (ops.py).

  quant_matmul     — int8 MAC with int32 accumulation (bit-serial numerics)
  alpha_composite  — volume-rendering transmittance walk
  decode_attention — flash-decoding over a long KV cache
  flash_attention  — prefill/train flash attention (scores stay in VMEM)
"""
from repro.kernels.ops import (
    alpha_composite,
    decode_attention,
    flash_attention,
    quant_matmul,
)

__all__ = [
    "alpha_composite",
    "decode_attention",
    "flash_attention",
    "quant_matmul",
]
