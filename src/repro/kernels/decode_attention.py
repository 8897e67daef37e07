"""Pallas TPU decode attention: one query token per sequence against a
(possibly ring-buffered) KV cache.

Decode is bandwidth-bound — the whole cache is streamed once. The kernel
keeps the q row resident in VMEM and tiles the cache along S with online
softmax (m, l, acc) in scratch, exactly the flash recurrence with Sq = 1.
GQA is exploited natively: the *kv-head* is the grid axis and all
``group`` q heads sharing it are processed against one cache tile —
cutting cache reads by the group factor vs. head-major layouts.

Grid: (batch, kv_heads, s_blocks) — s innermost/sequential.
q: (B, G, KV, hd) grouped layout; k/v cache: (B, S, KV, hd).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, window: int, bs: int, groups: int,
):
    si = pl.program_id(2)
    ns = pl.num_programs(2)
    cache_len = len_ref[0]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s_start = si * bs
    run = s_start < cache_len
    if window > 0:
        run = run & (s_start + bs - 1 >= cache_len - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale  # (G, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bs, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, bs)
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, (groups, bs), 1)
        mask = pos < cache_len
        if window > 0:
            mask = mask & (pos >= cache_len - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_prev = l_scr[...][:, :1]
        l_scr[...] = jnp.broadcast_to(l_prev * corr + p.sum(-1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(si == ns - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "block_s", "interpret")
)
def decode_attention(
    q: jnp.ndarray,        # (B, 1, H, hd)
    k_cache: jnp.ndarray,  # (B, S, KV, hd)
    v_cache: jnp.ndarray,  # (B, S, KV, hd)
    cache_len: jnp.ndarray,  # () int32 — valid entries
    *,
    window: int = 0,
    scale: Optional[float] = None,
    block_s: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, _, h, hd = q.shape
    s_max, kv = k_cache.shape[1], k_cache.shape[2]
    groups = h // kv
    scale = float(scale if scale is not None else hd ** -0.5)
    bs = min(block_s, s_max)
    pad = (-s_max) % bs
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ns = k_cache.shape[1] // bs
    # grouped q layout: (B, G, KV, hd)
    qg = q[:, 0].reshape(b, kv, groups, hd).transpose(0, 2, 1, 3)
    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1)[:1], (1,))

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, bs=bs, groups=groups
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, kv, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((1, groups, 1, hd), lambda bi, ki, si: (bi, 0, ki, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda bi, ki, si: (bi, si, ki, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda bi, ki, si: (bi, si, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, groups, 1, hd), lambda bi, ki, si: (bi, 0, ki, 0)),
        out_shape=jax.ShapeDtypeStruct((b, groups, kv, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((groups, _LANES), jnp.float32),
            pltpu.VMEM((groups, _LANES), jnp.float32),
            pltpu.VMEM((groups, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(clen, qg, k_cache, v_cache)
    # (B, G, KV, hd) → (B, 1, H, hd)
    return out.transpose(0, 2, 1, 3).reshape(b, 1, h, hd)
