"""Serving paths for all six families: KV/state cache layout, prefill
(fills the cache, returns last-token logits) and single-token decode.

Cache layout is *stacked per layer* (leading ``L`` dim) so both prefill
and decode run a ``lax.scan`` over ``(block_params, cache_layer)`` — the
lowered HLO is one block body regardless of depth, which keeps the 512-
device dry-run compile tractable.

Sliding-window attention uses a **ring buffer** of size ``window``: slot
for absolute position ``p`` is ``p % window`` (matches
:func:`repro.models.attention.gqa_decode`). A 500k-context decode for a
SWA/SSM arch therefore holds O(window)/O(1) state, not O(S) — this is
what makes the ``long_500k`` cells runnable for sub-quadratic archs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import sharding
from .attention import (
    _mla_q,
    chunked_attention,
    gqa_decode,
    gqa_project_qkv,
    mla_decode,
)
from .common import KeyGen, apply_norm, apply_rope, rms_norm
from .config import ModelConfig
from .mlp import mlp, moe_layer
from .ssm import _causal_conv as mamba_conv
from .ssm import _split_in, mamba_decode, mamba_init_cache, ssd_chunked
from .xlstm import (
    _slstm_cell,
    mlstm_chunked,
    mlstm_decode,
    mlstm_init_cache,
    slstm_decode,
    slstm_init_cache,
)
from .xlstm import _causal_conv as xlstm_conv

PyTree = Any


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _ring(cfg: ModelConfig, max_len: int) -> int:
    """Effective cache length: ring of size `window` under SWA."""
    return min(max_len, cfg.swa_window) if cfg.swa_window else max_len


def _stack_zeros(n: int, shape, dtype):
    return jnp.zeros((n, *shape), dtype)


# ===================================================================== caches

def _attn_cache_stack(cfg: ModelConfig, n: int, batch: int, m: int, use_mla: bool):
    dt = _dt(cfg)
    if use_mla:
        a = cfg.mla
        return {
            "c_kv": _stack_zeros(n, (batch, m, a.kv_lora_rank), dt),
            "k_rope": _stack_zeros(n, (batch, m, a.qk_rope_head_dim), dt),
        }
    kv, hd = cfg.n_kv_heads, cfg.head_dim_
    return {
        "k": _stack_zeros(n, (batch, m, kv, hd), dt),
        "v": _stack_zeros(n, (batch, m, kv, hd), dt),
    }


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, *, memory_len: int = 0
) -> PyTree:
    """Empty cache for a serving session of ≤ max_len absolute positions."""
    m = _ring(cfg, max_len)
    dt = _dt(cfg)
    fam = cfg.family
    cache: Dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    if fam == "dense":
        cache["layers"] = _attn_cache_stack(cfg, cfg.n_layers, batch, m, False)
    elif fam == "moe":
        k = cfg.moe.first_k_dense
        use_mla = cfg.mla is not None
        if k:
            cache["dense_layers"] = _attn_cache_stack(cfg, k, batch, m, use_mla)
        cache["layers"] = _attn_cache_stack(cfg, cfg.n_layers - k, batch, m, use_mla)
    elif fam == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.n_layers - n_cross
        kv, hd = cfg.n_kv_heads, cfg.head_dim_
        cache["layers"] = _attn_cache_stack(cfg, n_self, batch, m, False)
        cache["cross"] = {
            "k": _stack_zeros(n_cross, (batch, memory_len, kv, hd), dt),
            "v": _stack_zeros(n_cross, (batch, memory_len, kv, hd), dt),
        }
    elif fam == "audio":
        kv, hd = cfg.n_kv_heads, cfg.head_dim_
        cache["layers"] = _attn_cache_stack(cfg, cfg.n_layers, batch, m, False)
        cache["cross"] = {
            "k": _stack_zeros(cfg.n_layers, (batch, memory_len, kv, hd), dt),
            "v": _stack_zeros(cfg.n_layers, (batch, memory_len, kv, hd), dt),
        }
    elif fam == "ssm":
        x = cfg.xlstm
        n_s = cfg.n_layers // x.slstm_every if x.slstm_every else 0
        n_m = cfg.n_layers - n_s
        one_m = mlstm_init_cache(cfg, batch)
        cache["mlstm"] = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_m, *a.shape)), one_m)
        if n_s:
            one_s = slstm_init_cache(cfg, batch)
            cache["slstm"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_s, *a.shape)), one_s
            )
    elif fam == "hybrid":
        one = mamba_init_cache(cfg, batch, dt)
        cache["mamba"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), one
        )
        every = cfg.shared_attn_every
        if every:
            n_sh = cfg.n_layers // every
            cache["shared"] = _attn_cache_stack(cfg, n_sh, batch, m, False)
    else:
        raise ValueError(fam)
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, memory_len: int = 0):
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, memory_len=memory_len)
    )


# ============================================================ cache writers

def _write_linear(cache_arr: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """Prefill fill from position 0 (cache assumed fresh)."""
    return jax.lax.dynamic_update_slice_in_dim(cache_arr, new.astype(cache_arr.dtype), 0, axis=1)


def _write_ring(cache_arr: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """Fill a ring buffer of size M with the last ≤M of S new entries.

    For S ≥ M the kept positions p ∈ [S−M, S) map bijectively onto slots
    p % M — a roll by (S−M) % M.  For S < M it is a plain prefix write.
    """
    m = cache_arr.shape[1]
    s = new.shape[1]
    if s < m:
        return _write_linear(cache_arr, new)
    tail = new[:, s - m :]
    rolled = jnp.roll(tail, shift=(s - m) % m, axis=1)
    return rolled.astype(cache_arr.dtype)


def _write(cfg: ModelConfig, cache_arr: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    return _write_ring(cache_arr, new) if cfg.swa_window else _write_linear(cache_arr, new)


# ====================================================== cross-attention K/V

def _cross_kv(p: PyTree, memory: jnp.ndarray, cfg: ModelConfig):
    mem = rms_norm(memory, p["k_input_norm"])
    k = jnp.einsum("bsd,dhk->bshk", mem, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", mem, p["wv"])
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"])
    return k, v


def _cross_apply(p: PyTree, x: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
    out = chunked_attention(q, k, v, causal=False)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if "gate" in p:
        y = jnp.tanh(p["gate"]).astype(y.dtype) * y
    return y


def _cross_block_cached(bp: PyTree, h, k, v, cfg):
    a_in = apply_norm(h, bp["attn_norm"], cfg.norm)
    h = h + _cross_apply(bp["attn"], a_in, k, v, cfg)
    m_in = apply_norm(h, bp["mlp_norm"], cfg.norm)
    return h + mlp(bp["mlp"], m_in, cfg.activation)


# ==================================================== dense-family prefill

def _gqa_prefill_layer(bp, h, positions, cfg, cl):
    """One attn+ffn layer: returns (h, filled cache layer)."""
    a_in = apply_norm(h, bp["attn_norm"], cfg.norm)
    q, k, v = gqa_project_qkv(bp["attn"], a_in, positions, cfg)
    out = chunked_attention(q, k, v, causal=True, window=cfg.swa_window)
    h = h + jnp.einsum("bshk,hkd->bsd", out, bp["attn"]["wo"])
    new_cl = {"k": _write(cfg, cl["k"], k), "v": _write(cfg, cl["v"], v)}
    m_in = apply_norm(h, bp["mlp_norm"], cfg.norm)
    if "moe" in bp:
        h = h + moe_layer(bp["moe"], m_in, cfg)
    else:
        h = h + mlp(bp["mlp"], m_in, cfg.activation)
    return sharding.constrain(h, "hidden"), new_cl


def _mla_prefill_layer(bp, h, positions, cfg, cl):
    m = cfg.mla
    p = bp["attn"]
    a_in = apply_norm(h, bp["attn_norm"], cfg.norm)
    q_nope, q_rope = _mla_q(p, a_in, positions, cfg)
    c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", a_in, p["w_dkv"]), p["kv_norm"])
    k_rope = apply_rope(
        jnp.einsum("bsd,dk->bsk", a_in, p["w_krope"])[:, :, None, :],
        positions,
        cfg.rope_theta,
    )
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    out = chunked_attention(q, k, v, causal=True, scale=scale)
    h = h + jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    new_cl = {
        "c_kv": _write(cfg, cl["c_kv"], c_kv),
        "k_rope": _write(cfg, cl["k_rope"], k_rope[:, :, 0, :]),
    }
    m_in = apply_norm(h, bp["mlp_norm"], cfg.norm)
    if "moe" in bp:
        h = h + moe_layer(bp["moe"], m_in, cfg)
    else:
        h = h + mlp(bp["mlp"], m_in, cfg.activation)
    return sharding.constrain(h, "hidden"), new_cl


def _attn_prefill_scan(blocks, cache_layers, h, positions, cfg, use_mla):
    layer = _mla_prefill_layer if use_mla else _gqa_prefill_layer
    return jax.lax.scan(
        lambda h, xs: layer(xs[0], h, positions, cfg, xs[1]), h, (blocks, cache_layers)
    )


def _attn_decode_scan(blocks, cache_layers, h, pos, cfg, use_mla):
    if CACHE_LAYOUT == "carry":
        return _attn_decode_carry(blocks, cache_layers, h, pos, cfg, use_mla)

    def body(h, xs):
        bp, cl = xs
        a_in = apply_norm(h, bp["attn_norm"], cfg.norm)
        dec = mla_decode if use_mla else gqa_decode
        y, new_cl = dec(bp["attn"], a_in, {**cl, "len": pos}, cfg)
        h = h + y
        m_in = apply_norm(h, bp["mlp_norm"], cfg.norm)
        if "moe" in bp:
            h = h + moe_layer(bp["moe"], m_in, cfg)
        else:
            h = h + mlp(bp["mlp"], m_in, cfg.activation)
        del new_cl["len"]
        return sharding.constrain(h, "decode_hidden"), new_cl

    return jax.lax.scan(body, h, (blocks, cache_layers))


# ==================================================== ssm / hybrid helpers

def _mamba_prefill(p, x, cfg):
    """Like mamba_block but returns (y, cache layer) with the final state."""
    s = cfg.ssm
    D = cfg.d_model
    di, nh, N = s.d_inner(D), s.n_heads(D), s.d_state
    proj = jnp.einsum("bsd,de->bse", x, p["w_in"])
    z, xbc, dt = _split_in(proj, di, N, nh)
    conv_tail = xbc[:, -(s.d_conv - 1) :, :]
    xbc = jax.nn.silu(mamba_conv(xbc, p["conv_w"], p["conv_b"]))
    xin, B_ssm, C_ssm = xbc[..., :di], xbc[..., di : di + N], xbc[..., di + N :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    xh = xin.reshape(*xin.shape[:2], nh, s.head_dim)
    y, h_final = ssd_chunked(xh, dt, a, B_ssm, C_ssm, chunk=s.chunk)
    y = y + xh.astype(jnp.float32) * p["d_skip"][:, None]
    y = y.reshape(*x.shape[:2], di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, {"conv": conv_tail.astype(x.dtype), "h": h_final}


def _mlstm_prefill(p, x, cfg):
    D = cfg.d_model
    nh = cfg.n_heads
    inner = int(cfg.xlstm.mlstm_proj_factor * D)
    Pd = inner // nh
    up = jnp.einsum("bsd,de->bse", x, p["w_up"])
    xg, xc = up[..., :inner], up[..., inner:]
    conv_tail = xc[:, -3:, :].astype(jnp.float32)
    xconv = jax.nn.silu(xlstm_conv(xc, p["conv_w"], p["conv_b"]))
    q = jnp.einsum("bse,ef->bsf", xconv, p["wq"]).reshape(*x.shape[:2], nh, Pd)
    k = jnp.einsum("bse,ef->bsf", xconv, p["wk"]).reshape(*x.shape[:2], nh, Pd)
    v = jnp.einsum("bse,ef->bsf", xc, p["wv"]).reshape(*x.shape[:2], nh, Pd)
    gates = jnp.einsum("bse,eg->bsg", xconv, p["w_if"])
    i_gate, f_gate = gates[..., :nh], gates[..., nh:]
    y, (C, n, m) = mlstm_chunked(q, k, v, i_gate, f_gate, chunk=cfg.xlstm.chunk)
    y = y.reshape(*x.shape[:2], inner).astype(x.dtype)
    y = rms_norm(y, p["out_norm"]) * jax.nn.silu(xg)
    out = jnp.einsum("bse,ed->bsd", y, p["w_down"])
    return out, {"conv": conv_tail, "C": C, "n": n, "m": m}


def _slstm_prefill(p, x, cfg):
    D = cfg.d_model
    nh = cfg.n_heads
    hd = D // nh
    B, S, _ = x.shape
    conv_tail = x[:, -3:, :].astype(jnp.float32)
    xconv = jax.nn.silu(xlstm_conv(x, p["conv_w"], p["conv_b"]))
    xg = jnp.einsum("bsd,dg->bsg", xconv, p["w_gates"])
    state0 = (
        jnp.zeros((B, nh, hd), jnp.float32),
        jnp.zeros((B, nh, hd), jnp.float32),
        jnp.zeros((B, nh, hd), jnp.float32),
        jnp.full((B, nh), -1e30, jnp.float32),
    )

    def step(state, xg_t):
        new = _slstm_cell(p, xg_t, state)
        return new, new[0]

    (hf, cf, nf, mf), hs = jax.lax.scan(step, state0, xg.transpose(1, 0, 2))
    y = hs.transpose(1, 0, 2, 3).reshape(B, S, D).astype(x.dtype)
    y = rms_norm(y, p["gn"])
    ff = jax.nn.silu(jnp.einsum("bsd,df->bsf", y, p["ff_gate"])) * jnp.einsum(
        "bsd,df->bsf", y, p["ff_up"]
    )
    out = jnp.einsum("bsf,fd->bsd", ff, p["ff_down"])
    return out, {"conv": conv_tail, "h": hf, "c": cf, "n": nf, "m": mf}


# =============================================================== prefill

def prefill(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, S) int32
    cache: PyTree,
    *,
    memory: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, PyTree]:
    """Process a fresh prompt; returns (last-token logits (B, V), cache)."""
    B, S = tokens.shape
    h = params["embed"][tokens].astype(_dt(cfg))
    h = sharding.constrain(h, "hidden")
    positions = jnp.arange(S)[None, :]
    fam = cfg.family
    new_cache: Dict[str, Any] = {"len": jnp.full((), S, jnp.int32)}

    if fam in ("dense", "moe"):
        use_mla = cfg.mla is not None
        if fam == "moe" and cfg.moe.first_k_dense:
            h, dl = _attn_prefill_scan(
                params["dense_blocks"], cache["dense_layers"], h, positions, cfg, use_mla
            )
            new_cache["dense_layers"] = dl
        h, layers = _attn_prefill_scan(
            params["blocks"], cache["layers"], h, positions, cfg, use_mla
        )
        new_cache["layers"] = layers

    elif fam == "vlm":
        k_every = cfg.cross_attn_every
        n_cross = cfg.n_layers // k_every
        n_self_per = k_every - 1
        self_grouped = jax.tree.map(
            lambda x: x.reshape(n_cross, n_self_per, *x.shape[1:]), params["blocks"]
        )
        cache_grouped = jax.tree.map(
            lambda x: x.reshape(n_cross, n_self_per, *x.shape[1:]), cache["layers"]
        )
        mem = memory.astype(_dt(cfg))

        def super_body(h, xs):
            selfs, cls, cross_bp = xs
            h, new_cls = _attn_prefill_scan(selfs, cls, h, positions, cfg, False)
            ck, cv = _cross_kv(cross_bp["attn"], mem, cfg)
            h = _cross_block_cached(cross_bp, h, ck, cv, cfg)
            return sharding.constrain(h, "hidden"), (new_cls, ck, cv)

        h, (cls, cks, cvs) = jax.lax.scan(
            super_body, h, (self_grouped, cache_grouped, params["cross_blocks"])
        )
        new_cache["layers"] = jax.tree.map(
            lambda x: x.reshape(n_cross * n_self_per, *x.shape[2:]), cls
        )
        new_cache["cross"] = {"k": cks.astype(_dt(cfg)), "v": cvs.astype(_dt(cfg))}

    elif fam == "audio":
        mem = encode(params, cfg, memory)

        def dec_body(h, xs):
            bp_self, bp_cross, cl = xs
            h, new_cl = _gqa_prefill_layer(bp_self, h, positions, cfg, cl)
            ck, cv = _cross_kv(bp_cross["attn"], mem, cfg)
            h = _cross_block_cached(bp_cross, h, ck, cv, cfg)
            return sharding.constrain(h, "hidden"), (new_cl, ck, cv)

        h, (cls, cks, cvs) = jax.lax.scan(
            dec_body, h, (params["blocks"], params["cross_blocks"], cache["layers"])
        )
        new_cache["layers"] = cls
        new_cache["cross"] = {"k": cks.astype(_dt(cfg)), "v": cvs.astype(_dt(cfg))}

    elif fam == "ssm":
        x = cfg.xlstm

        def m_body(h, xs):
            bp, _cl = xs
            y, new_cl = _mlstm_prefill(bp["cell"], apply_norm(h, bp["norm"], cfg.norm), cfg)
            return sharding.constrain(h + y, "hidden"), new_cl

        if x.slstm_every:
            groups = cfg.n_layers // x.slstm_every
            per = x.slstm_every - 1
            m_grouped = jax.tree.map(
                lambda a: a.reshape(groups, per, *a.shape[1:]), params["mlstm_blocks"]
            )
            mc_grouped = jax.tree.map(
                lambda a: a.reshape(groups, per, *a.shape[1:]), cache["mlstm"]
            )

            def super_body(h, xs):
                ms, mcs, sl, _sc = xs
                h, new_mc = jax.lax.scan(m_body, h, (ms, mcs))
                y, new_sc = _slstm_prefill(
                    sl["cell"], apply_norm(h, sl["norm"], cfg.norm), cfg
                )
                return sharding.constrain(h + y, "hidden"), (new_mc, new_sc)

            h, (mcs, scs) = jax.lax.scan(
                super_body,
                h,
                (m_grouped, mc_grouped, params["slstm_blocks"], cache["slstm"]),
            )
            new_cache["mlstm"] = jax.tree.map(
                lambda a: a.reshape(groups * per, *a.shape[2:]), mcs
            )
            new_cache["slstm"] = scs
        else:
            h, mcs = jax.lax.scan(m_body, h, (params["mlstm_blocks"], cache["mlstm"]))
            new_cache["mlstm"] = mcs

    elif fam == "hybrid":
        every = cfg.shared_attn_every
        shared = params["shared_attn"]

        def mamba_body(h, xs):
            bp, _cl = xs
            y, new_cl = _mamba_prefill(bp["mixer"], apply_norm(h, bp["norm"], cfg.norm), cfg)
            return sharding.constrain(h + y, "hidden"), new_cl

        if every:
            groups = cfg.n_layers // every
            g_params = jax.tree.map(
                lambda a: a.reshape(groups, every, *a.shape[1:]), params["mamba_blocks"]
            )
            g_cache = jax.tree.map(
                lambda a: a.reshape(groups, every, *a.shape[1:]), cache["mamba"]
            )

            def super_body(h, xs):
                mb, mc, sc = xs
                h, new_mc = jax.lax.scan(mamba_body, h, (mb, mc))
                h, new_sc = _gqa_prefill_layer(shared, h, positions, cfg, sc)
                return h, (new_mc, new_sc)

            h, (mcs, scs) = jax.lax.scan(super_body, h, (g_params, g_cache, cache["shared"]))
            new_cache["mamba"] = jax.tree.map(
                lambda a: a.reshape(groups * every, *a.shape[2:]), mcs
            )
            new_cache["shared"] = scs
        else:
            h, mcs = jax.lax.scan(mamba_body, h, (params["mamba_blocks"], cache["mamba"]))
            new_cache["mamba"] = mcs
    else:
        raise ValueError(fam)

    h_last = apply_norm(h[:, -1:, :], params["final_norm"], cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", h_last, head)[:, 0]
    return sharding.constrain(logits, "logits_last"), new_cache


def encode(params: PyTree, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """Audio/enc-dec encoder over stub frame embeddings → memory states."""
    from .transformer import _dense_block  # local import to avoid cycle

    mem = apply_norm(frames.astype(_dt(cfg)), params["enc_embed_norm"], cfg.norm)
    enc_pos = jnp.arange(mem.shape[1])[None, :]

    def enc_body(m, bp):
        return _dense_block(bp, m, enc_pos, cfg, causal=False), None

    mem, _ = jax.lax.scan(enc_body, mem, params["encoder"])
    return apply_norm(mem, params["enc_final_norm"], cfg.norm)


# ================================================================ decode

def decode_step(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, 1) int32 — the most recent sampled token
    cache: PyTree,
) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step; returns (logits (B, V), updated cache)."""
    pos = cache["len"]
    h = params["embed"][tokens].astype(_dt(cfg))
    fam = cfg.family
    new_cache: Dict[str, Any] = {"len": pos + 1}

    if fam in ("dense", "moe"):
        use_mla = cfg.mla is not None
        if fam == "moe" and cfg.moe.first_k_dense:
            h, dl = _attn_decode_scan(
                params["dense_blocks"], cache["dense_layers"], h, pos, cfg, use_mla
            )
            new_cache["dense_layers"] = dl
        h, layers = _attn_decode_scan(
            params["blocks"], cache["layers"], h, pos, cfg, use_mla
        )
        new_cache["layers"] = layers

    elif fam == "vlm":
        k_every = cfg.cross_attn_every
        n_cross = cfg.n_layers // k_every
        n_self_per = k_every - 1
        self_grouped = jax.tree.map(
            lambda x: x.reshape(n_cross, n_self_per, *x.shape[1:]), params["blocks"]
        )
        cache_grouped = jax.tree.map(
            lambda x: x.reshape(n_cross, n_self_per, *x.shape[1:]), cache["layers"]
        )

        def super_body(h, xs):
            selfs, cls, cross_bp, ck, cv = xs
            h, new_cls = _attn_decode_scan(selfs, cls, h, pos, cfg, False)
            h = _cross_block_cached(cross_bp, h, ck, cv, cfg)
            return h, new_cls

        h, cls = jax.lax.scan(
            super_body,
            h,
            (
                self_grouped,
                cache_grouped,
                params["cross_blocks"],
                cache["cross"]["k"],
                cache["cross"]["v"],
            ),
        )
        new_cache["layers"] = jax.tree.map(
            lambda x: x.reshape(n_cross * n_self_per, *x.shape[2:]), cls
        )
        new_cache["cross"] = cache["cross"]

    elif fam == "audio":
        def dec_body(h, xs):
            bp_self, bp_cross, cl, ck, cv = xs
            a_in = apply_norm(h, bp_self["attn_norm"], cfg.norm)
            y, new_cl = gqa_decode(bp_self["attn"], a_in, {**cl, "len": pos}, cfg)
            h = h + y
            m_in = apply_norm(h, bp_self["mlp_norm"], cfg.norm)
            h = h + mlp(bp_self["mlp"], m_in, cfg.activation)
            h = _cross_block_cached(bp_cross, h, ck, cv, cfg)
            del new_cl["len"]
            return h, new_cl

        h, cls = jax.lax.scan(
            dec_body,
            h,
            (
                params["blocks"],
                params["cross_blocks"],
                cache["layers"],
                cache["cross"]["k"],
                cache["cross"]["v"],
            ),
        )
        new_cache["layers"] = cls
        new_cache["cross"] = cache["cross"]

    elif fam == "ssm":
        x = cfg.xlstm

        def m_body(h, xs):
            bp, cl = xs
            y, new_cl = mlstm_decode(bp["cell"], apply_norm(h, bp["norm"], cfg.norm), cl, cfg)
            return h + y, new_cl

        if x.slstm_every:
            groups = cfg.n_layers // x.slstm_every
            per = x.slstm_every - 1
            m_grouped = jax.tree.map(
                lambda a: a.reshape(groups, per, *a.shape[1:]), params["mlstm_blocks"]
            )
            mc_grouped = jax.tree.map(
                lambda a: a.reshape(groups, per, *a.shape[1:]), cache["mlstm"]
            )

            def super_body(h, xs):
                ms, mcs, sl, sc = xs
                h, new_mc = jax.lax.scan(m_body, h, (ms, mcs))
                y, new_sc = slstm_decode(
                    sl["cell"], apply_norm(h, sl["norm"], cfg.norm), sc, cfg
                )
                return h + y, (new_mc, new_sc)

            h, (mcs, scs) = jax.lax.scan(
                super_body,
                h,
                (m_grouped, mc_grouped, params["slstm_blocks"], cache["slstm"]),
            )
            new_cache["mlstm"] = jax.tree.map(
                lambda a: a.reshape(groups * per, *a.shape[2:]), mcs
            )
            new_cache["slstm"] = scs
        else:
            h, mcs = jax.lax.scan(m_body, h, (params["mlstm_blocks"], cache["mlstm"]))
            new_cache["mlstm"] = mcs

    elif fam == "hybrid":
        every = cfg.shared_attn_every
        shared = params["shared_attn"]

        def mamba_body(h, xs):
            bp, cl = xs
            y, new_cl = mamba_decode(bp["mixer"], apply_norm(h, bp["norm"], cfg.norm), cl, cfg)
            return h + y, new_cl

        if every:
            groups = cfg.n_layers // every
            g_params = jax.tree.map(
                lambda a: a.reshape(groups, every, *a.shape[1:]), params["mamba_blocks"]
            )
            g_cache = jax.tree.map(
                lambda a: a.reshape(groups, every, *a.shape[1:]), cache["mamba"]
            )

            def super_body(h, xs):
                mb, mc, sc = xs
                h, new_mc = jax.lax.scan(mamba_body, h, (mb, mc))
                a_in = apply_norm(h, shared["attn_norm"], cfg.norm)
                y, new_sc = gqa_decode(shared["attn"], a_in, {**sc, "len": pos}, cfg)
                h = h + y
                m_in = apply_norm(h, shared["mlp_norm"], cfg.norm)
                h = h + mlp(shared["mlp"], m_in, cfg.activation)
                del new_sc["len"]
                return h, (new_mc, new_sc)

            h, (mcs, scs) = jax.lax.scan(
                super_body, h, (g_params, g_cache, cache["shared"])
            )
            new_cache["mamba"] = jax.tree.map(
                lambda a: a.reshape(groups * every, *a.shape[2:]), mcs
            )
            new_cache["shared"] = scs
        else:
            h, mcs = jax.lax.scan(mamba_body, h, (params["mamba_blocks"], cache["mamba"]))
            new_cache["mamba"] = mcs
    else:
        raise ValueError(fam)

    h = apply_norm(h, params["final_norm"], cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)[:, 0]
    return sharding.constrain(logits, "logits_last"), new_cache


# -- carry-layout decode (§Perf hillclimb: nemotron decode_32k) ----------------------
#
# H: scanning cache layers as xs/ys stacks a full-layer copy per step;
# carrying the stacked cache through the loop and (a) DUS-ing only the new
# token at (layer, :, pos) and (b) slicing the layer for attention keeps
# the write O(token) and the read O(layer) — the bandwidth floor.

CACHE_LAYOUT = "scan"  # "scan" | "carry"


def _gqa_decode_carry(p, x, cache_k, cache_v, li, pos, cfg):
    s_max = cache_k.shape[2]
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    from .attention import decode_attention as _dec_attn

    q, k, v = gqa_project_qkv(p, x, positions, cfg)
    slot = (pos % s_max) if cfg.swa_window else pos
    zero = jnp.zeros((), jnp.int32)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k[None].astype(cache_k.dtype), (li, zero, slot, zero, zero)
    )
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v[None].astype(cache_v.dtype), (li, zero, slot, zero, zero)
    )
    k_layer = jax.lax.dynamic_index_in_dim(cache_k, li, 0, keepdims=False)
    v_layer = jax.lax.dynamic_index_in_dim(cache_v, li, 0, keepdims=False)
    new_len = pos + 1
    eff = jnp.minimum(new_len, s_max) if cfg.swa_window else new_len
    out = _dec_attn(q, k_layer, v_layer, eff, window=0)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, cache_k, cache_v


def _mla_decode_carry(p, x, c_kv_all, k_rope_all, li, pos, cfg):
    m = cfg.mla
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, positions, cfg)
    c_new = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"])
    kr_new = apply_rope(
        jnp.einsum("bsd,dk->bsk", x, p["w_krope"])[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    zero = jnp.zeros((), jnp.int32)
    c_kv_all = jax.lax.dynamic_update_slice(
        c_kv_all, c_new[None].astype(c_kv_all.dtype), (li, zero, pos, zero)
    )
    k_rope_all = jax.lax.dynamic_update_slice(
        k_rope_all, kr_new[None].astype(k_rope_all.dtype), (li, zero, pos, zero)
    )
    c_kv = jax.lax.dynamic_index_in_dim(c_kv_all, li, 0, keepdims=False)
    k_rope = jax.lax.dynamic_index_in_dim(k_rope_all, li, 0, keepdims=False)
    new_len = pos + 1
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["w_uk"])
    s_lat = jnp.einsum("bhr,bmr->bhm", q_lat, c_kv)
    s_rope = jnp.einsum("bhk,bmk->bhm", q_rope[:, 0], k_rope)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = (s_lat + s_rope) * scale
    valid = jnp.arange(c_kv.shape[1])[None, :] < new_len
    s = jnp.where(valid[:, None, :], s.astype(jnp.float32), -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhk,bkr->bhr", prob, c_kv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhk->bhk", o_lat.astype(x.dtype), p["w_uv"])
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return y, c_kv_all, k_rope_all


def _attn_decode_carry(blocks, cache_layers, h, pos, cfg, use_mla):
    n_layers = jax.tree.leaves(blocks)[0].shape[0]

    def body(carry, xs):
        h, cache = carry
        bp, li = xs
        a_in = apply_norm(h, bp["attn_norm"], cfg.norm)
        if use_mla:
            y, ck, kr = _mla_decode_carry(
                bp["attn"], a_in, cache["c_kv"], cache["k_rope"], li, pos, cfg
            )
            cache = {"c_kv": ck, "k_rope": kr}
        else:
            y, ck, cv = _gqa_decode_carry(
                bp["attn"], a_in, cache["k"], cache["v"], li, pos, cfg
            )
            cache = {"k": ck, "v": cv}
        h = h + y
        m_in = apply_norm(h, bp["mlp_norm"], cfg.norm)
        if "moe" in bp:
            h = h + moe_layer(bp["moe"], m_in, cfg)
        else:
            h = h + mlp(bp["mlp"], m_in, cfg.activation)
        return (sharding.constrain(h, "decode_hidden"), cache), None

    (h, cache), _ = jax.lax.scan(
        body, (h, cache_layers), (blocks, jnp.arange(n_layers))
    )
    return h, cache


# -- pipeline-parallel decode (§Perf hillclimb: nemotron decode_32k) -----------------
#
# H: with (data × model)-FSDP weights, every decode step re-gathers 42 GB
# of weights per device over the data axis. Pipelining layers over the
# data axis instead makes weights STATIONARY: shard s owns layers
# [s·L/16, (s+1)·L/16) whole (model-TP'd), microbatches flow through
# stages via one tiny collective_permute per round. This function is one
# *steady-state GPipe round*: every stage applies its local layers to its
# resident microbatch and hands it on — per-token throughput cost.
#
# shard_map is manual over "data" only (axis_names); the "model" axis
# stays auto, so the per-layer attention/MLP keep their GSPMD tensor
# parallelism unchanged.

def decode_step_pp(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, 1) — entering microbatch tokens per stage slot
    cache: PyTree,        # {"layers": L-sharded stacks, "pp_h": (B,1,D), "len"}
    rules,
) -> Tuple[jnp.ndarray, PyTree]:
    assert cfg.family == "dense", "PP decode experiment covers the dense family"
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    stage_axis = "data"
    n_stages = rules.mesh_sizes[stage_axis]
    L = cfg.n_layers
    assert L % n_stages == 0
    pos = cache["len"]

    mb = tokens.shape[0] // n_stages  # microbatch per stage slot

    def stage_fn(blocks_local, cache_local, h_in, tok_local, embed, head, final_norm):
        sid = jax.lax.axis_index(stage_axis)
        is_first = sid == 0
        is_last = sid == n_stages - 1
        # stage 0 ingests the entering microbatch
        h_tok = embed[tok_local].astype(_dt(cfg))
        h = jnp.where(is_first, h_tok, h_in)
        # the cache at this stage holds ALL microbatches' KV for its
        # layers; the one resident this round is offset by the stage id
        m_idx = ((n_stages - sid) % n_stages) * mb
        cache_mb = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, m_idx, mb, axis=1),
            cache_local,
        )
        # inside the manual 'data' axis the batch-sharding constraints are
        # meaningless — drop them; the auto 'model' axis propagates via GSPMD
        with sharding.use_rules(None):
            h, new_mb = _attn_decode_scan(blocks_local, cache_mb, h, pos, cfg, False)
        new_cache = jax.tree.map(
            lambda full, upd: jax.lax.dynamic_update_slice_in_dim(
                full, upd.astype(full.dtype), m_idx, axis=1
            ),
            cache_local, new_mb,
        )
        # stage L−1 emits logits for the exiting microbatch
        h_last = apply_norm(h, final_norm, cfg.norm)
        logits = jnp.einsum("bsd,dv->bsv", h_last, head)[:, 0]
        logits = jnp.where(is_last, logits, jnp.zeros_like(logits))
        h_next = jax.lax.ppermute(
            h, stage_axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return h_next, logits, new_cache

    blocks = params["blocks"]
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    in_specs = (
        jax.tree.map(lambda _: P(stage_axis), blocks),          # L over stages
        jax.tree.map(lambda _: P(stage_axis), cache["layers"]),
        P(stage_axis, None, None),                               # pp_h (B,1,D)
        P(stage_axis, None),                                     # tokens
        P(None, None),                                           # embed
        P(None, None),                                           # head
        jax.tree.map(lambda _: P(None), params["final_norm"]),
    )
    out_specs = (
        P(stage_axis, None, None),
        P(stage_axis, None),
        jax.tree.map(lambda _: P(stage_axis), cache["layers"]),
    )
    h_next, logits, new_layers = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names={stage_axis},
        check_vma=False,
    )(
        blocks, cache["layers"], cache["pp_h"], tokens,
        params["embed"], head, params["final_norm"],
    )
    new_cache = {"len": pos + 1, "layers": new_layers, "pp_h": h_next}
    return logits, new_cache
