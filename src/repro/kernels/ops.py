"""Jit'd public wrappers for the Pallas kernels.

On CPU these run in interpret mode (kernel body executed in Python) —
correctness validation only; on TPU they always compile to Mosaic, and
asking for interpret mode there is an error.  Model code opts in via
``use_pallas=True``; the dry-run and tests default to the pure-jnp
references in ``repro.kernels.ref``.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.expert_ffn import expert_ffn_pallas as _expert_ffn
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.residual_codec import residual_int8_pallas as _residual_int8
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas as _rwkv6_scan


def _interpret(flag) -> bool:
    """Interpret mode is the CPU test path: the default there, refused on
    an accelerator backend, where every kernel compiles."""
    on_cpu = jax.default_backend() == "cpu"
    if flag and not on_cpu:
        raise ValueError("Pallas interpret mode is a CPU-only test path; "
                         f"the {jax.default_backend()} backend compiles "
                         "the kernel")
    return on_cpu if flag is None else flag


def _largest_divisor(n: int, pref: int, align: int = 8) -> int:
    """Largest block <= pref that divides n (the kernels assert exact
    tiling), preferring multiples of ``align`` — TPU f32 tiles want
    8-aligned sublane dims, and capacities are 8-aligned by construction
    (e.g. C=136 tiles as 8, where the old ``min(128, C)`` choice asserted
    out).  Falls back to the largest plain divisor when no aligned one
    exists (tiny or odd n, exercised only in interpret mode)."""
    b = min(pref, n)
    b -= b % align
    while b >= align and n % b:
        b -= align
    if b >= align:
        return b
    b = min(pref, n)
    while n % b:
        b -= 1
    return max(b, 1)


@partial(jax.jit, static_argnames=("act", "interpret"))
def expert_ffn_pallas(buf, w_gate, w_up, w_down, *, act="silu",
                      interpret=None):
    interpret = _interpret(interpret)
    C = buf.shape[1]
    d = buf.shape[2]
    f = w_gate.shape[-1]
    if d <= 8192:
        # d whole per tile (bit-identical to the pre-block_d kernel).  The
        # three (d, bf) weight tiles dominate VMEM and are double-
        # buffered, so bf shrinks with the weights' item size: 512 for
        # bf16, 256 for f32 — f32 at 512 overflows v5e's 16 MiB scoped
        # VMEM at DiT-MoE-XL/G widths (d=1152/1408, f=4608/5632)
        block_c, block_d = 128, None
        block_f = 1024 // w_gate.dtype.itemsize
    else:
        # VMEM budget for huge d: the d-wide tiles are the (bc, d) f32
        # accumulator and the (bf, d) down tile, so both bc and bf shrink
        # (d=16384: acc 4 MiB + wd 8 MiB) while block_d caps the x/gate/up
        # tiles that no longer grow with d_model at all
        block_c, block_f, block_d = 64, 128, _largest_divisor(d, 2048)
    return _expert_ffn(buf, w_gate, w_up, w_down, act=act,
                       block_c=_largest_divisor(C, block_c),
                       block_f=_largest_divisor(f, block_f),
                       block_d=block_d,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def residual_int8_pallas(value, base, *, interpret=None):
    """Fused wire-codec quantize-pack: (N, d) payload + residual base ->
    (q int8, per-row f32 scale, receiver-side reconstruction)."""
    interpret = _interpret(interpret)
    return _residual_int8(value, base, interpret=interpret)


def _pick_block(n: int, pref: int = 128) -> int:
    """Largest power-of-two block <= pref that divides n."""
    b = min(pref, n)
    while n % b:
        b //= 2
    return max(b, 1)


@partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                   "interpret"))
def flash_attention_pallas(q, k, v, *, causal=False, window=None,
                           softcap=None, interpret=None):
    interpret = _interpret(interpret)
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  block_q=_pick_block(q.shape[1]),
                  block_k=_pick_block(k.shape[1]),
                  interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def rwkv6_scan(r, k, v, logw, u, s0, *, interpret=None):
    interpret = _interpret(interpret)
    return _rwkv6_scan(r, k, v, logw, u, s0,
                       chunk=_pick_block(r.shape[2]), interpret=interpret)
