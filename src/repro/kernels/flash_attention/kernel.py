"""Pallas TPU kernel: online-softmax (flash) attention.

Tiling: grid = (B·Hq, Tq/BQ, Tk/BK) with the KV axis innermost.  Running
max/sum and the unnormalized accumulator live in revisited *output* blocks
(their block index is constant along the KV axis, so Pallas keeps them in
VMEM across inner steps); the final KV step normalizes.  GQA is expressed in
the K/V BlockSpec index_map: query head h reads kv head h // group — no
repeat/copy of K/V in HBM.

Causal and sliding-window masks are applied with block-level iota; fully
masked (future) blocks still execute but contribute zero — on real hardware
the Mosaic grid could early-skip via `pl.when` on the whole block, which is
how the causal speedup is realized.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

Array = jax.Array

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(
    q_ref,   # (1, BQ, D)
    k_ref,   # (1, BK, D)
    v_ref,   # (1, BK, D)
    o_ref,   # (1, BQ, D)   unnormalized accumulator → final output
    m_ref,   # (1, BQ)      running max
    l_ref,   # (1, BQ)      running sum
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    prefix_len: int,
    kv_offset: int,
    kv_len: int,
    block_q: int,
    block_k: int,
    n_k_blocks: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                     # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                     # (BK, D)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                            # (BQ, BK)

    q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    q_idx = q_idx + kv_offset
    k_idx = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_idx < kv_len
    vis = jnp.ones_like(mask)
    if causal:
        vis = q_idx >= k_idx
    if window is not None:
        vis &= (q_idx - k_idx) < window
    if prefix_len > 0:
        vis |= k_idx < prefix_len
    mask &= vis
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[0]                                    # (BQ,)
    l_prev = l_ref[0]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)

    acc = o_ref[0] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] = acc[None]
    m_ref[...] = m_new[None]
    l_ref[...] = l_new[None]

    @pl.when(kj == n_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[0], 1e-30)
        o_ref[...] = (o_ref[0] / denom[:, None])[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "hq", "hkv", "causal", "window", "prefix_len", "kv_offset", "kv_len",
        "scale", "block_q", "block_k", "interpret",
    ),
)
def flash_attention_flat(
    q: Array,   # (BH, Tq, D)  flattened batch·q-heads
    k: Array,   # (BHkv, Tk, D)
    v: Array,   # (BHkv, Tk, D)
    *,
    hq: int | None = None,
    hkv: int | None = None,
    scale: float,
    causal: bool,
    window: Optional[int],
    prefix_len: int = 0,
    kv_offset: int = 0,
    kv_len: int,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> Array:
    bh, tq, d = q.shape
    bhkv, tk, _ = k.shape
    assert tq % block_q == 0 and tk % block_k == 0, (tq, tk)
    group = bh // bhkv if hq is None else hq // hkv
    n_k_blocks = tk // block_k
    grid = (bh, tq // block_q, n_k_blocks)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        prefix_len=prefix_len,
        kv_offset=kv_offset,
        kv_len=kv_len,
        block_q=block_q,
        block_k=block_k,
        n_k_blocks=n_k_blocks,
    )

    def kv_map(h, i, j):
        return (h // group, j, 0)

    out, _, _ = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q), lambda h, i, j: (h, i)),
            pl.BlockSpec((1, block_q), lambda h, i, j: (h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq), jnp.float32),
        ],
        interpret=interpret_default(interpret),
    )(q, k, v)
    return out
