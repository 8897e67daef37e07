"""Feed-forward blocks: SwiGLU / squared-ReLU / GeLU MLPs and Mixture of
Experts with scatter-based dispatch.

MoE dispatch deliberately avoids the GShard one-hot einsum ('td,tec->ecd'),
whose FLOPs (T·E·C·D) dwarf the expert compute for large E (DeepSeek: 160
experts ⇒ ~1000× the useful FLOPs). Instead tokens are scattered into a
static (E·C, D) buffer by their (expert, position-in-expert) slot and
gathered back — O(T·k·D) data movement, zero matmul overhead, static
shapes, and a clean expert-sharded layout for pjit.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import sharding
from .common import dense_init


def act_fn(name: str):
    if name == "swiglu":
        return None  # handled structurally (gate * up)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(name)


def mlp_params(key_gen, d_model: int, d_ff: int, activation: str, dtype) -> Dict[str, Any]:
    p = {
        "w_up": dense_init(key_gen(), (d_model, d_ff), dtype),
        "w_down": dense_init(key_gen(), (d_ff, d_model), dtype),
    }
    if activation == "swiglu":
        p["w_gate"] = dense_init(key_gen(), (d_model, d_ff), dtype)
    return p


def mlp(p: Dict[str, Any], x: jnp.ndarray, activation: str) -> jnp.ndarray:
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["w_gate"])) * jnp.einsum(
            "...d,df->...f", x, p["w_up"]
        )
    else:
        h = act_fn(activation)(jnp.einsum("...d,df->...f", x, p["w_up"]))
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# -- Mixture of Experts -----------------------------------------------------------

def moe_params(key_gen, cfg, dtype) -> Dict[str, Any]:
    m = cfg.moe
    D, F, E = cfg.d_model, m.expert_ff, m.num_experts
    p: Dict[str, Any] = {
        "router": dense_init(key_gen(), (D, E), dtype),
        "w_up": dense_init(key_gen(), (E, D, F), dtype, fan_in=D),
        "w_down": dense_init(key_gen(), (E, F, D), dtype, fan_in=F),
    }
    if cfg.activation == "swiglu":
        p["w_gate"] = dense_init(key_gen(), (E, D, F), dtype, fan_in=D)
    if m.num_shared:
        p["shared"] = mlp_params(
            key_gen, D, F * m.num_shared, cfg.activation, dtype
        )
    return p


def _positions_within_group(flat_e: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """pos[i] = #{j < i : flat_e[j] == flat_e[i]} — the capacity slot rank.

    Sort-based: O(N log N) compute, O(N) memory. The one-hot+cumsum
    formulation materializes an (N, E) tensor — 4 TB at 1M tokens × 160
    experts — which dominated the MoE prefill footprint.
    """
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((n_groups,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e]
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)


def _expert_ffn(p: Dict[str, Any], xe: jnp.ndarray, activation: str) -> jnp.ndarray:
    """xe: (E, C, D) -> (E, C, D), batched over experts."""
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * jnp.einsum(
            "ecd,edf->ecf", xe, p["w_up"]
        )
    else:
        h = act_fn(activation)(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_layer(p: Dict[str, Any], x: jnp.ndarray, cfg) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D). Scatter-based top-k dispatch with capacity;
    dispatches to the expert-parallel shard_map path when MOE_IMPL == "ep"
    and a mesh is active (§Perf hillclimb)."""
    rules = sharding.current_rules()
    if MOE_IMPL == "ep" and rules is not None and rules.mesh is not None:
        return moe_layer_ep(p, x, cfg, rules)
    m = cfg.moe
    if MOE_DECODE == "sparse" and x.shape[0] * x.shape[1] * m.top_k <= m.num_experts:
        B, S, D = x.shape
        return _moe_decode_sparse(p, x.reshape(B * S, D), cfg).reshape(B, S, D)
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    T = B * S
    C = max(int(T * K / E * m.capacity_factor), 4)

    xt = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert (sort-based —
    # no (T·K, E) one-hot materialization)
    flat_e = idx.reshape(-1)  # (T*K,)
    pos = _positions_within_group(flat_e, E)
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)  # dropped → trash row

    token_id = jnp.repeat(jnp.arange(T), K)
    buf = jnp.zeros((E * C + 1, D), xt.dtype).at[slot].set(xt[token_id])
    expert_in = buf[: E * C].reshape(E, C, D)
    expert_in = sharding.constrain(expert_in, "moe_experts")
    expert_out = _expert_ffn(p, expert_in, cfg.activation)
    expert_out = sharding.constrain(expert_out, "moe_experts")
    flat_out = jnp.concatenate(
        [expert_out.reshape(E * C, D), jnp.zeros((1, D), expert_out.dtype)], axis=0
    )
    y_tk = flat_out[slot] * gate_vals.reshape(-1)[:, None].astype(expert_out.dtype)
    y = y_tk.reshape(T, K, D).sum(axis=1)

    if m.num_shared:
        y = y + mlp(p["shared"], xt, cfg.activation)
    return y.reshape(B, S, D)


def moe_aux_loss(p: Dict[str, Any], x: jnp.ndarray, cfg) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style): E·Σ f_e·p_e."""
    m = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, m.num_experts, dtype=jnp.float32), axis=0)
    pmean = probs.mean(axis=0)
    return m.num_experts * jnp.sum(f * pmean)


# -- Expert-parallel MoE (shard_map) -----------------------------------------------
#
# §Perf hillclimb (EXPERIMENTS.md): the GSPMD scatter dispatch cross-shards
# the (E·C, D) buffer, inserting all-reduces over the data axis that
# dominate the collective term at 236B scale. Expert parallelism makes the
# dispatch *local*: experts shard over the "data" axis (each shard owns
# E/n_ep experts whole), tokens move via one all_to_all each way, and the
# F-dim stays sharded over "model" with a single psum after w_down.
# Traffic per layer ≈ T·K·cf·D each way vs. re-gathering E·3DF weights.

MOE_IMPL = "dense"  # "dense" (GSPMD scatter) | "ep" (shard_map all_to_all)


def _moe_ep_body(xt, router, w_gate, w_up, w_down, shared, cfg, n_ep, axis):
    """Per-shard body under shard_map. xt: (T_loc, D) local tokens."""
    m = cfg.moe
    T, D = xt.shape
    E, K = m.num_experts, m.top_k
    E_loc = E // n_ep
    c_send = max(int(T * K / n_ep * m.capacity_factor), 4)
    c_loc = max(int(T * K / E_loc * m.capacity_factor), 4)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)          # (T, K) global expert ids
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- outbound: pack per-destination-shard send buffers ---------------
    dest = (idx // E_loc).reshape(-1)                  # (T·K,) owning shard
    local_e = (idx % E_loc).reshape(-1)
    pos = _positions_within_group(dest, n_ep)
    keep = pos < c_send
    slot = jnp.where(keep, dest * c_send + pos, n_ep * c_send)
    token_id = jnp.repeat(jnp.arange(T), K)
    send_x = jnp.zeros((n_ep * c_send + 1, D), xt.dtype).at[slot].set(xt[token_id])
    send_e = jnp.zeros((n_ep * c_send + 1,), jnp.int32).at[slot].set(local_e + 1)
    recv_x = jax.lax.all_to_all(
        send_x[: n_ep * c_send].reshape(n_ep, c_send, D), axis, 0, 0
    )
    recv_e = jax.lax.all_to_all(
        send_e[: n_ep * c_send].reshape(n_ep, c_send), axis, 0, 0
    )

    # --- local dispatch into the shard's own experts ----------------------
    rows = n_ep * c_send
    rx = recv_x.reshape(rows, D)
    rl = recv_e.reshape(rows) - 1                      # −1 = empty slot
    valid = rl >= 0
    pos2 = _positions_within_group(jnp.where(valid, rl, E_loc), E_loc + 1)
    keep2 = valid & (pos2 < c_loc)
    slot2 = jnp.where(keep2, rl * c_loc + pos2, E_loc * c_loc)
    buf = jnp.zeros((E_loc * c_loc + 1, D), xt.dtype).at[slot2].set(rx)
    expert_in = buf[: E_loc * c_loc].reshape(E_loc, c_loc, D)
    expert_out = _expert_ffn(
        {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        if w_gate is not None
        else {"w_up": w_up, "w_down": w_down},
        expert_in,
        cfg.activation,
    )  # (E_loc, c_loc, D) — PARTIAL over the model axis (w_down F-sharded)

    # --- return path (still partial sums; psum deferred to the end) -------
    back_rows = jnp.concatenate(
        [expert_out.reshape(E_loc * c_loc, D), jnp.zeros((1, D), expert_out.dtype)], 0
    )[slot2]
    back = jax.lax.all_to_all(back_rows.reshape(n_ep, c_send, D), axis, 0, 0)
    y_tk = jnp.concatenate(
        [back.reshape(n_ep * c_send, D), jnp.zeros((1, D), back.dtype)], 0
    )[slot]
    y = (y_tk * gate_vals.reshape(-1)[:, None].astype(y_tk.dtype)).reshape(T, K, D).sum(1)

    if m.num_shared:
        y = y + mlp(shared, xt, cfg.activation)        # also partial over model
    return jax.lax.psum(y, "model")


def moe_layer_ep(p: Dict[str, Any], x: jnp.ndarray, cfg, rules) -> jnp.ndarray:
    """Expert-parallel MoE: dispatch via shard_map over the data axis."""
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    dax = rules.data          # batch axes, e.g. ("data",) or ("pod", "data")
    ep_axis = dax[-1]         # experts shard over the innermost data axis
    n_ep = rules.mesh_sizes[ep_axis]
    B, S, D = x.shape

    w_gate = p.get("w_gate")
    shared = p.get("shared")
    batch_spec = dax if len(dax) > 1 else dax[0]

    def body(xl, router, wg, wu, wd, sh):
        T_loc = xl.shape[0] * xl.shape[1]
        y = _moe_ep_body(
            xl.reshape(T_loc, D), router, wg, wu, wd, sh, cfg, n_ep, ep_axis
        )
        return y.reshape(xl.shape)

    # shared-expert mlp: w_up/w_gate (D, F): F over model; w_down (F, D)
    def _shared_specs(sh):
        return {
            k: (P("model", None) if k == "w_down" else P(None, "model"))
            for k in sh
        }

    in_specs = (
        P(batch_spec, None, None),
        P(None, None),
        P(ep_axis, None, "model") if w_gate is not None else None,
        P(ep_axis, None, "model"),
        P(ep_axis, "model", None),
        _shared_specs(shared) if shared is not None else None,
    )
    args = (x, p["router"], w_gate, p["w_up"], p["w_down"], shared)
    # drop None args (shard_map specs must match the pytree)
    keep = [i for i, a in enumerate(args) if a is not None]
    f_args = tuple(args[i] for i in keep)
    f_specs = tuple(in_specs[i] for i in keep)

    def wrapper(*packed):
        full = [None] * len(args)
        for i, a in zip(keep, packed):
            full[i] = a
        return body(*full)

    return jax.shard_map(
        wrapper,
        mesh=mesh,
        in_specs=f_specs,
        out_specs=P(batch_spec, None, None),
        check_vma=False,
    )(*f_args)


# -- Sparse MoE decode (§Perf hillclimb: mixtral long_500k) --------------------------
#
# H: at tiny decode batches the capacity-buffer path touches ALL E experts'
# weights; gathering only the top-k experts' matrices via dynamic slices
# reads K/E of the weight bytes. Used when T·K ≤ E (else dense wins).

MOE_DECODE = "dense"  # "dense" | "sparse"


def _moe_decode_sparse(p: Dict[str, Any], xt: jnp.ndarray, cfg) -> jnp.ndarray:
    m = cfg.moe
    T, D = xt.shape
    K = m.top_k
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    def token_out(x_t, idx_t, gv_t):
        ys = []
        for i in range(K):  # K static & tiny
            e = idx_t[i]
            wu = jax.lax.dynamic_index_in_dim(p["w_up"], e, 0, keepdims=False)
            wd = jax.lax.dynamic_index_in_dim(p["w_down"], e, 0, keepdims=False)
            if "w_gate" in p:
                wg = jax.lax.dynamic_index_in_dim(p["w_gate"], e, 0, keepdims=False)
                h = jax.nn.silu(x_t @ wg) * (x_t @ wu)
            else:
                h = act_fn(cfg.activation)(x_t @ wu)
            ys.append(gv_t[i].astype(x_t.dtype) * (h @ wd))
        return sum(ys)

    y = jax.vmap(token_out)(xt, idx, gate_vals)
    if m.num_shared:
        y = y + mlp(p["shared"], xt, cfg.activation)
    return y
