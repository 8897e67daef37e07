"""Roofline terms from a compiled dry-run artifact (no hardware needed).

  compute term    = HLO_FLOPs / (chips × peak FLOP/s)
  memory term     = HLO_bytes / (chips × HBM bandwidth)
  collective term = collective_bytes / (chips × link bandwidth)

``compiled.cost_analysis()`` runs on the *partitioned* module, so its
flops/bytes are per-device; the collective bytes are parsed per-device
from the partitioned HLO text the same way. The three terms are therefore
directly comparable per-device seconds.

MODEL_FLOPS uses the 6·N·D convention (2·N·D for inference) with N =
active params, so the MODEL_FLOPS / HLO_FLOPs ratio exposes remat
recompute and attention/dispatch overheads.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict

# TPU v5e hardware constants (per chip)
@dataclass(frozen=True)
class _HW:
    peak_flops: float = 197e12      # bf16
    hbm_bw: float = 819e9           # bytes/s
    link_bw: float = 50e9           # bytes/s per ICI link
    hbm_bytes: float = 16e9


HW = _HW()

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# one HLO instruction: "%name = TYPE opcode(OPERANDS...)," possibly fused
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, Any]:
    """Sum operand bytes of every collective op in (partitioned) HLO text."""
    per_op: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        _, rhs = s.split(" = ", 1)
        m = re.match(r"(?:\([^)]*\)|\S+)\s+([\w-]+)\(", rhs)
        if not m:
            continue
        op = m.group(1)
        base = op
        for suffix in ("-start", "-done", "-update"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
        if base not in _COLLECTIVES:
            continue
        if op.endswith("-done"):  # operands counted on the -start op
            continue
        # operand shapes appear inline inside the call parens
        args = rhs[m.end():]
        depth = 1
        for i, ch in enumerate(args):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    args = args[:i]
                    break
        total = 0
        for dm in _SHAPE_RE.finditer(args):
            total += _shape_bytes(dm.group(1), dm.group(2))
        per_op[base] += total
        counts[base] += 1
    return {
        "bytes_by_type": per_op,
        "counts_by_type": counts,
        "total_bytes": sum(per_op.values()),
        "total_count": sum(counts.values()),
    }


def model_flops(cfg, cell) -> float:
    """6·N_active·tokens (train) / 2·N_active·tokens (inference).

    Enc-dec: encoder params see ``encoder_seq`` frames, decoder params the
    text sequence — counting all params × text tokens would overstate the
    useful FLOPs (the seamless ratio was >1 before this split).
    """
    _, active = cfg.param_count()
    mult = 6.0 if cell.kind == "train" else 2.0
    if cell.kind == "decode":
        dec_tokens = cell.global_batch
    else:
        dec_tokens = cell.global_batch * cell.seq_len
    if cfg.is_enc_dec:
        # split active params proportionally to layer counts
        enc_frac = cfg.n_encoder_layers / (cfg.n_encoder_layers + 2 * cfg.n_layers)
        enc_tokens = cell.global_batch * cfg.encoder_seq
        if cell.kind == "decode":
            enc_tokens = 0  # encoder ran at prefill
        return mult * active * (
            enc_frac * enc_tokens + (1 - enc_frac) * dec_tokens
        )
    return mult * active * dec_tokens


def analyze_compiled(compiled, cfg, cell, mesh) -> Dict[str, Any]:
    from . import hlo_parse

    chips = mesh.devices.size
    cost = compiled.cost_analysis()
    # XLA's cost_analysis counts while bodies once — recorded for reference
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    # trip-count-corrected per-device costs from the partitioned HLO
    parsed = hlo_parse.analyze(compiled.as_text())
    flops_dev = parsed["flops"]
    bytes_dev = parsed["hbm_bytes"]
    coll_dev = parsed["collective_wire_bytes"]

    compute_s = flops_dev / HW.peak_flops
    memory_s = bytes_dev / HW.hbm_bw
    collective_s = coll_dev / HW.link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    step_s = max(terms.values()) if terms else 0.0
    mflops = model_flops(cfg, cell)
    useful_ratio = mflops / max(flops_dev * chips, 1.0)
    mfu = mflops / max(chips * HW.peak_flops * step_s, 1e-30) if step_s else 0.0

    out = {
        "chips": chips,
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": float(parsed["collective_bytes"]),
        "collective_wire_bytes_per_device": coll_dev,
        "collective_detail": {
            "bytes_by_type": parsed["collective_bytes_by_type"],
            "wire_bytes_by_type": parsed["collective_wire_bytes_by_type"],
            "counts_by_type": parsed["collective_counts_by_type"],
            "total_count": parsed["collective_count"],
        },
        "xla_cost_analysis_raw": {"flops": raw_flops, "bytes": raw_bytes},
        "compute_term_s": compute_s,
        "memory_term_s": memory_s,
        "collective_term_s": collective_s,
        "dominant": dominant,
        "model_flops": mflops,
        "useful_flops_ratio": useful_ratio,
        "roofline_fraction": mfu,
    }
    # theoretical per-device bandwidth floor: every step must at least
    # read the (sharded) weights once; decode additionally streams the
    # cache. Distance to this floor is the §Perf target for decode cells.
    total_params, _ = cfg.param_count()
    floor_bytes = total_params * 2.0 / chips  # bf16 weights
    if cell.kind == "decode":
        m = min(cell.seq_len, cfg.swa_window) if cfg.swa_window else cell.seq_len
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            kvb = (
                cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
                if cfg.mla
                else 2 * cfg.n_kv_heads * cfg.head_dim_
            )
            floor_bytes += cell.global_batch * m * kvb * 2.0 * cfg.n_layers / chips
    out["memory_floor_s"] = floor_bytes / HW.hbm_bw
    # kernel-adjusted view: named_scope traffic → Pallas kernel boundary
    adj = kernel_adjusted(
        {"hbm_bytes": bytes_dev, "hbm_by_kernel_scope": parsed["hbm_by_kernel_scope"]},
        cfg, cell, chips,
    )
    mem_k = adj["memory_term_kernel_s"]
    step_k = max(compute_s, mem_k, collective_s)
    terms_k = {"compute": compute_s, "memory": mem_k, "collective": collective_s}
    out.update(
        kernel_adjusted=adj,
        memory_term_kernel_s=mem_k,
        dominant_kernel=max(terms_k, key=terms_k.get),
        roofline_fraction_kernel=(
            mflops / max(chips * HW.peak_flops * step_k, 1e-30) if step_k else 0.0
        ),
    )
    return out


# =====================================================================
# Kernel-adjusted roofline
#
# The pure-jnp reference paths materialize attention scores / SSD chunk
# tensors in HBM; the Pallas kernels (repro.kernels) keep those tiles in
# VMEM on TPU. Model code tags kernel-eligible regions with
# jax.named_scope("kernel_*"); the parser measures their HLO HBM bytes,
# and here we substitute each scope's traffic with the *kernel boundary*
# (q/k/v/o etc. — what the kernel actually DMAs), giving the adjusted
# memory term the TPU deployment would see.
# =====================================================================

_PASS_FACTOR = {"train": 4.0, "prefill": 1.0, "decode": 1.0}
# train: fwd + remat-fwd + backward (reads q,k,v,o,do; writes dq,dk,dv) ≈ 4×


def kernel_boundary_bytes(cfg, cell) -> Dict[str, float]:
    """GLOBAL bytes per step each Pallas kernel would move, by scope."""
    B, S = cell.global_batch, cell.seq_len
    fam = cfg.family
    H, KV, hd, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_, cfg.d_model
    f = _PASS_FACTOR[cell.kind]
    out: Dict[str, float] = {}

    def flash(n_calls, sq, sk, h_q, kv, d_qk, d_v):
        # q + o (H-headed) and k + v (kv-headed), bf16
        return n_calls * f * 2.0 * (
            sq * h_q * (d_qk + d_v) + sk * kv * (d_qk + d_v)
        ) * B

    if fam in ("dense", "moe", "vlm", "audio", "hybrid"):
        sq = 1 if cell.kind == "decode" else S
        if cell.kind == "decode":
            # decode uses the decode-attention kernel over the cache
            m = min(S, cfg.swa_window) if cfg.swa_window else S
            if cfg.mla is None:
                n_layers = {
                    "dense": cfg.n_layers,
                    "moe": cfg.n_layers,
                    "vlm": cfg.n_layers - cfg.n_layers // max(cfg.cross_attn_every, 1),
                    "audio": cfg.n_layers,
                    "hybrid": (cfg.n_layers // cfg.shared_attn_every)
                    if cfg.shared_attn_every
                    else 0,
                }[fam]
                out["kernel_decode_attn"] = n_layers * 2.0 * B * m * KV * hd * 2.0
            # cross-attn decode (vlm/audio) flows through the flash scope
            if fam == "vlm":
                n_cross = cfg.n_layers // cfg.cross_attn_every
                out["kernel_flash_attn"] = flash(
                    n_cross, 1, cfg.num_image_tokens, H, KV, hd, hd
                )
            if fam == "audio":
                out["kernel_flash_attn"] = flash(
                    cfg.n_layers, 1, cfg.encoder_seq, H, KV, hd, hd
                )
        else:
            if cfg.mla is not None:
                m = cfg.mla
                d_qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                # expanded k/v are H-headed at the kernel boundary
                out["kernel_flash_attn"] = flash(
                    cfg.n_layers, sq, S, H, H, d_qk, m.v_head_dim
                )
            elif fam == "dense" or fam == "moe":
                out["kernel_flash_attn"] = flash(cfg.n_layers, sq, S, H, KV, hd, hd)
            elif fam == "vlm":
                n_cross = cfg.n_layers // cfg.cross_attn_every
                n_self = cfg.n_layers - n_cross
                out["kernel_flash_attn"] = flash(n_self, sq, S, H, KV, hd, hd) + flash(
                    n_cross, sq, cfg.num_image_tokens, H, KV, hd, hd
                )
            elif fam == "audio":
                enc = flash(cfg.n_encoder_layers, cfg.encoder_seq, cfg.encoder_seq, H, KV, hd, hd)
                dec = flash(cfg.n_layers, sq, S, H, KV, hd, hd)
                cross = flash(cfg.n_layers, sq, cfg.encoder_seq, H, KV, hd, hd)
                out["kernel_flash_attn"] = enc + dec + cross
            elif fam == "hybrid":
                n_sh = cfg.n_layers // cfg.shared_attn_every if cfg.shared_attn_every else 0
                out["kernel_flash_attn"] = flash(n_sh, sq, S, H, KV, hd, hd)

    if fam == "hybrid" and cell.kind != "decode":
        s = cfg.ssm
        di, nh, N = s.d_inner(D), s.n_heads(D), s.d_state
        per = B * S * (di * 2 + nh * 4 + 2 * N * 2 + di * 4)  # x,dt,B,C,y
        out["kernel_ssd_scan"] = cfg.n_layers * f * float(per)
    if fam == "ssm" and cell.kind != "decode":
        x = cfg.xlstm
        inner = int(x.mlstm_proj_factor * D)
        nh = cfg.n_heads
        n_s = cfg.n_layers // x.slstm_every if x.slstm_every else 0
        n_m = cfg.n_layers - n_s
        per = B * S * (3 * inner * 2 + 2 * nh * 4 + inner * 4)  # q,k,v,i,f,y
        out["kernel_mlstm_scan"] = n_m * f * float(per)
    return out


def kernel_adjusted(rec: Dict[str, Any], cfg, cell, chips: int) -> Dict[str, Any]:
    """Adjusted memory term: measured scope traffic → kernel boundary."""
    scopes = rec.get("hbm_by_kernel_scope") or {}
    boundary = kernel_boundary_bytes(cfg, cell)
    measured = sum(scopes.values())
    replaced = sum(boundary.get(k, 0.0) / chips for k in scopes)
    adj_bytes = max(rec["hbm_bytes"] - measured + replaced, 0.0)
    return {
        "scope_bytes_measured": {k: float(v) for k, v in scopes.items()},
        "kernel_boundary_bytes_per_device": {
            k: v / chips for k, v in boundary.items()
        },
        "hbm_bytes_adjusted": adj_bytes,
        "memory_term_kernel_s": adj_bytes / HW.hbm_bw,
    }
