"""Pallas TPU kernels for the perf-critical layers (validated in interpret
mode on CPU; tiled for VMEM/MXU on real hardware):

    pivot        FormOpt section 5.4 row->column pivot, on device
    flashattn    flash causal GQA attention with its backward (train /
                 prefill; JAX's splash attention kernels)
    decode_attn  one-token attention over a long KV cache (serving)
    rwkv6_scan   RWKV-6 WKV recurrence, chunk-tiled
    mamba2_ssd   Mamba-2 SSD chunk-parallel dual form

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper
with a use_pallas/ref switch), ref.py (pure-jnp oracle); flashattn's ops.py
wraps JAX's splash attention and has no kernel.py.
"""

from .pivot.ops import pivot, pivot_columns
from .flashattn.ops import attention as flash_attention
from .decode_attn.ops import decode_attn
from .rwkv6_scan.ops import wkv
from .mamba2_ssd.ops import ssd

__all__ = ["pivot", "pivot_columns", "flash_attention", "decode_attn",
           "wkv", "ssd"]
