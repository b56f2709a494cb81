"""jit'd public wrapper for flash attention.

``impl``:
  "pallas"    — the Pallas kernel (interpret-mode on CPU, Mosaic on TPU);
  "reference" — the O(T²) jnp oracle;
  "chunked"   — pure-JAX online-softmax scan (same math as the kernel but
                built from lax.scan; this is the path the multi-pod dry-run
                lowers, since Mosaic does not lower on the CPU backend).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, NEG_INF, flash_attention_flat
from .ref import attention_ref

Array = jax.Array


def _pad_axis(x: Array, axis: int, multiple: int) -> Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def chunked_attention(
    q: Array,              # (B, Hq, Tq, D)
    k: Array,              # (B, Hkv, Tk, D)
    v: Array,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    kv_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    unroll: bool = False,
    context_sharding=None,
) -> Array:
    """Flash-style online softmax in pure JAX: scan over KV blocks with a
    FlashAttention custom VJP (chunked_vjp.py), so forward peak memory is
    O(BQ·BK) per (batch, head) and the backward saves only (q, k, v, out,
    lse) — no per-step accumulators.

    ``context_sharding`` optionally shards the *query-block* dim (context /
    sequence parallelism): when the head count does not divide the tensor
    axis, sharding queries over it keeps attention compute partitioned
    (K/V are all-gathered — ring-attention pipelining is a further step)."""
    from .chunked_vjp import chunked_attention_core

    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale

    tq_p = tq + ((-tq) % block_q)
    tk_p = tk + ((-tk) % block_k)
    qp = _pad_axis(q, 2, block_q).astype(jnp.float32)
    # K/V stay at input precision (bf16 from the model): per-block upcast
    # happens inside the core, halving the context-parallel all-gather and
    # the custom-VJP residuals vs an eager fp32 cast (§Perf phi4 #2).
    kp = _pad_axis(k, 2, block_k)
    vp = _pad_axis(v, 2, block_k)

    nq, nk = tq_p // block_q, tk_p // block_k
    # GQA group-aware layout: fold the query-head groups into the q-block
    # dim instead of repeating K/V — K/V stay at hkv heads (group× fewer
    # bytes on every K/V gather and dK/dV reduction).
    qb = qp.reshape(b, hkv, group, nq, block_q, d).reshape(
        b, hkv, group * nq, block_q, d
    )
    kb = kp.reshape(b, hkv, nk, block_k, d)
    vb = vp.reshape(b, hkv, nk, block_k, d)
    if context_sharding is not None:
        qb = jax.lax.with_sharding_constraint(qb, context_sharding)

    out = chunked_attention_core(
        qb, kb, vb, tk, causal, window, prefix_len, kv_offset,
        block_q, block_k, scale, unroll, nq,
    )
    if context_sharding is not None:
        out = jax.lax.with_sharding_constraint(out, context_sharding)
    out = out.reshape(b, hkv, group, nq, block_q, d).reshape(b, hq, tq_p, d)
    out = out[:, :, :tq]
    return out.astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix_len", "kv_offset", "scale",
                     "impl", "interpret", "block_q", "block_k", "unroll",
                     "context_sharding"),
)
def flash_attention(
    q: Array,              # (B, Hq, Tq, D)
    k: Array,              # (B, Hkv, Tk, D)
    v: Array,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    kv_offset: int = 0,
    scale: Optional[float] = None,
    impl: str = "pallas",
    interpret: bool | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    unroll: bool = False,
    context_sharding=None,
) -> Array:
    if impl == "reference":
        return attention_ref(q, k, v, causal=causal, window=window,
                             prefix_len=prefix_len, kv_offset=kv_offset,
                             scale=scale)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 prefix_len=prefix_len, kv_offset=kv_offset,
                                 scale=scale, block_q=block_q, block_k=block_k,
                                 unroll=unroll, context_sharding=context_sharding)

    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    scale_v = (d ** -0.5) if scale is None else scale
    qp = _pad_axis(q, 2, block_q)
    kp = _pad_axis(k, 2, block_k)
    vp = _pad_axis(v, 2, block_k)
    tq_p, tk_p = qp.shape[2], kp.shape[2]

    out = flash_attention_flat(
        qp.reshape(b * hq, tq_p, d),
        kp.reshape(b * hkv, tk_p, d),
        vp.reshape(b * hkv, tk_p, d),
        hq=hq,
        hkv=hkv,
        scale=scale_v,
        causal=causal,
        window=window,
        prefix_len=prefix_len,
        kv_offset=kv_offset,
        kv_len=tk,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    out = out.reshape(b, hq, tq_p, d)[:, :, :tq]
    return out.astype(q.dtype)
