"""Flash attention (fwd) Pallas kernel: GQA + causal + local window + softcap.

Tiling: grid = (batch, q_heads, q_blocks); the KV sequence is walked inside
the kernel with ``jax.lax.fori_loop`` over VMEM-resident KV blocks, carrying
the streaming-softmax state (m, l, acc) in registers/VMEM — the standard
IO-aware schedule: HBM traffic is O(S·d) per head instead of O(S²).

Block sizes default to (q=128, kv=128) — MXU-aligned (128x128 systolic
array) and comfortably inside the ~16 MB/core VMEM for head_dim <= 256:
q_blk·hd + 2·kv_blk·hd + q_blk·kv_blk floats ≈ 0.3 MB at fp32.

ref.py oracle: ``mha_ref`` (dense masked softmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.ref import mha_ref  # noqa: F401  (back-compat)

NEG = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_blk: int, causal: bool,
            window: int, softcap: float, q_blk: int, seq_k: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)

    q = q_ref[0, 0]                        # [q_blk, hd]
    hd = q.shape[-1]
    q_pos = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, 1), 0)

    nkv = seq_k // kv_blk

    def body(kv_i, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(kv_i * kv_blk, kv_blk), :]
        v = v_ref[0, 0, pl.ds(kv_i * kv_blk, kv_blk), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s = s / (hd ** 0.5)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = kv_i * kv_blk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_blk), 1)
        mask = jnp.ones((q_blk, kv_blk), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG)
        mb = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - mb)
        corr = jnp.exp(m - mb)
        l2 = l * corr + p.sum(axis=1, keepdims=True)
        acc2 = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return mb, l2, acc2

    m0 = jnp.full((q_blk, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q_blk, 1), jnp.float32)
    a0 = jnp.zeros((q_blk, hd), jnp.float32)
    if causal:
        # only KV blocks at or before this q block contribute
        hi = jnp.minimum((qi + 1) * q_blk + kv_blk - 1, seq_k) // kv_blk
    else:
        hi = nkv
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "q_blk", "kv_blk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_blk: int = 128,
                    kv_blk: int = 128, interpret: bool = True) -> jax.Array:
    """q [B, H, Sq, hd]; k/v [B, KH, Sk, hd] (GQA: H % KH == 0)."""
    b, h, sq, hd = q.shape
    kh, sk = k.shape[1], k.shape[2]
    rep = h // kh
    q_blk = min(q_blk, sq)
    kv_blk = min(kv_blk, sk)
    assert sq % q_blk == 0 and sk % kv_blk == 0

    out = pl.pallas_call(
        functools.partial(_kernel, kv_blk=kv_blk, causal=causal,
                          window=window, softcap=softcap, q_blk=q_blk,
                          seq_k=sk),
        grid=(b, h, sq // q_blk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, hd), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, sk, hd),
                         lambda b_, h_, i: (b_, h_ // rep, 0, 0)),
            pl.BlockSpec((1, 1, sk, hd),
                         lambda b_, h_, i: (b_, h_ // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, hd),
                               lambda b_, h_, i: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out
