"""Pallas TPU Mamba2 SSD chunked scan.

The SSD recurrence is chunk-parallel: within a chunk the output is a
masked (decay-weighted) matmul — MXU work — and only the (N × P) state
crosses chunks. The kernel maps chunks onto the innermost *sequential*
grid dim with the state in VMEM scratch, so the state never round-trips
to HBM (the pure-jnp scan writes it back every chunk).

Grid: (batch, heads, chunks) — chunks innermost.
Per-chunk tiles: x (L, P), dt/la (L,), B/C (L, N); scratch h (N, P) f32.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, la_ref, b_ref, c_ref,  # VMEM tiles
    y_ref, hout_ref,                      # outputs
    h_scr,                                # VMEM scratch state (N, P) f32
    *, chunk: int,
):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)   # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)    # (L,)
    la = la_ref[0, :, 0].astype(jnp.float32)    # (L,) = dt * a  (≤ 0)
    bm = b_ref[0].astype(jnp.float32)           # (L, N)
    cm = c_ref[0].astype(jnp.float32)           # (L, N)

    cum = jnp.cumsum(la)                        # (L,)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj
    T = jnp.where(causal, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
    CB = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    W = T * CB * dt[None, :]
    y_intra = jax.lax.dot(W, x, preferred_element_type=jnp.float32)  # (L, P)
    h = h_scr[...]
    y_inter = jax.lax.dot(cm, h, preferred_element_type=jnp.float32) * jnp.exp(cum)[:, None]
    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    last = cum[-1]
    w_end = jnp.exp(last - cum) * dt            # (L,)
    h_add = jax.lax.dot_general(
        bm, x * w_end[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (N, P)
    h_new = jnp.exp(last) * h + h_add
    h_scr[...] = h_new

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    xh: jnp.ndarray,     # (B, S, nh, P)
    dt: jnp.ndarray,     # (B, S, nh) softplus'd
    a: jnp.ndarray,      # (nh,) negative decay
    B_ssm: jnp.ndarray,  # (B, S, N)
    C_ssm: jnp.ndarray,  # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,nh,P) f32, final state (B,nh,N,P) f32)."""
    Bb, S, nh, P = xh.shape
    N = B_ssm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    la = dt * a[None, None, :]  # (B, S, nh)

    y, h = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(Bb, nh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, chunk, N), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, S, nh, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, nh, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xh, dt, la, B_ssm, C_ssm)
    return y, h
