"""Each Pallas kernel at the widths of a registered config that would call it.

``kernel_case(name)`` gives the kernel as a function of its array arguments,
those arguments (random, from a seed) and the ``ref.py`` oracle over the
same arguments.  The chip smoke run executes the cases and compares them;
the compile test lowers the same kernels for a described TPU.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .decode_attn.ops import decode_attn
from .decode_attn.ref import decode_attention_ref
from .flashattn.ops import attention
from .flashattn.ref import attention_ref
from .mamba2_ssd.ops import ssd
from .mamba2_ssd.ref import ssd_ref
from .pivot.ops import pivot
from .rwkv6_scan.ops import wkv
from .rwkv6_scan.ref import wkv_ref

__all__ = ["WIDTHS", "KernelCase", "kernel_case"]

WIDTHS: Dict[str, dict] = {
    # the smollm-360m train feed: rows of 1024 int32 tokens
    "pivot": {"source": "smollm-360m feed", "rows": 4096, "width": 1024},
    "flashattn": {"source": "smollm-360m", "B": 1, "S": 1024, "H": 15,
                  "KV": 5, "hd": 64},
    "decode_attn": {"source": "qwen2-1.5b", "B": 4, "S": 1024, "H": 12,
                    "KV": 2, "hd": 128, "length": 700},
    "rwkv6_scan": {"source": "rwkv6-3b", "B": 1, "S": 256, "H": 40,
                   "hd": 64},
    "mamba2_ssd": {"source": "zamba2-7b", "B": 1, "S": 256, "H": 112,
                   "hd": 64, "N": 64},
}


@dataclass
class KernelCase:
    name: str
    source: str
    kernel: Callable          # kernel(*args, interpret=...)
    ref: Callable             # ref(*args)
    args: Tuple[jax.Array, ...]


def kernel_case(name: str, dims: Optional[dict] = None,
                seed: int = 0) -> KernelCase:
    d = dims or WIDTHS[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = jax.random.normal
    if name == "pivot":
        rows = jax.random.randint(ks[0], (d["rows"], d["width"]), 0, 1 << 30,
                                  jnp.int32)
        return KernelCase(name, d["source"],
                          lambda x, interpret=False: pivot(x, interpret=interpret),
                          lambda x: x.T, (rows,))
    if name == "flashattn":
        q = normal(ks[0], (d["B"], d["S"], d["H"], d["hd"]), jnp.bfloat16)
        k = normal(ks[1], (d["B"], d["S"], d["KV"], d["hd"]), jnp.bfloat16)
        v = normal(ks[2], k.shape, jnp.bfloat16)
        return KernelCase(
            name, d["source"],
            lambda q, k, v, interpret=False: attention(q, k, v,
                                                       interpret=interpret),
            attention_ref, (q, k, v))
    if name == "decode_attn":
        q = normal(ks[0], (d["B"], d["H"], d["hd"]), jnp.bfloat16)
        kc = normal(ks[1], (d["B"], d["S"], d["KV"], d["hd"]), jnp.bfloat16)
        vc = normal(ks[2], kc.shape, jnp.bfloat16)
        n = jnp.asarray(d["length"], jnp.int32)
        return KernelCase(
            name, d["source"],
            lambda q, kc, vc, n, interpret=False: decode_attn(
                q, kc, vc, n, interpret=interpret),
            decode_attention_ref, (q, kc, vc, n))
    if name == "rwkv6_scan":
        shape = (d["B"], d["S"], d["H"], d["hd"])
        r, k, v = (normal(ks[i], shape) for i in range(3))
        w = jax.nn.sigmoid(normal(ks[3], shape)) * 0.9 + 0.05
        u = normal(ks[4], (d["H"], d["hd"])) * 0.1
        s0 = normal(ks[5], (d["B"], d["H"], d["hd"], d["hd"])) * 0.1
        return KernelCase(
            name, d["source"],
            lambda *a, interpret=False: wkv(*a, interpret=interpret),
            wkv_ref, (r, k, v, w, u, s0))
    if name == "mamba2_ssd":
        B, S, H, hd, N = d["B"], d["S"], d["H"], d["hd"], d["N"]
        x = normal(ks[0], (B, S, H, hd))
        dt = jax.nn.softplus(normal(ks[1], (B, S, H)))
        A = -jnp.exp(normal(ks[2], (H,)) * 0.3)
        Bm = normal(ks[3], (B, S, N)) * 0.5
        Cm = normal(ks[4], (B, S, N)) * 0.5
        D = jnp.ones((H,))
        s0 = normal(ks[5], (B, H, hd, N)) * 0.1
        return KernelCase(
            name, d["source"],
            lambda *a, interpret=False: ssd(*a, interpret=interpret),
            ssd_ref, (x, dt, A, Bm, Cm, D, s0))
    raise ValueError(f"unknown kernel {name!r}; have {sorted(WIDTHS)}")
