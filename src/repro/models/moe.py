"""Mixture-of-Experts FFN: token-choice top-k routing with fixed capacity.

Dispatch is sort-based (argsort by expert id + rank-within-expert), NOT the
GShard one-hot-einsum formulation: the einsum dispatch materializes a
(T, E, C) mask and — worse for this repo's roofline analysis — is counted by
XLA cost analysis as 2·T·E·C·D fake FLOPs that would swamp the useful expert
FLOPs.  Sort+scatter dispatch keeps HLO_FLOPs ≈ useful FLOPs.

Two distribution paths:
  * auto (default): plain code + sharding_constraint on the (E, C, D) buffer;
    GSPMD inserts the collectives.  This is the paper-faithful baseline.
  * shard_map (cfg.moe_shard_map): explicit expert-parallel all-to-all over
    the "model" axis — the beyond-paper optimized schedule (§Perf).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init, mlp, mlp_init


def moe_init(key, cfg):
    E, D, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "w_gate": dense_init(ks[1], (E, D, F), dt),
        "w_up": dense_init(ks[2], (E, D, F), dt),
        "w_down": dense_init(ks[3], (E, F, D), dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], D, cfg.n_shared_experts * F, dt)
    return p


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    c = int(math.ceil(T * k / E * cf))
    return max(8, min(c, T))  # never below a small floor, never above T


def _dispatch_indices(flat_e, E, C):
    """flat_e: (N,) expert id per (token, choice) slot.
    Returns (buffer_slot, keep) where buffer_slot in [0, E*C] (E*C = dropped)."""
    N = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.cumsum(counts) - counts                  # start of each expert
    rank = jnp.arange(N) - offsets[se]
    keep_sorted = rank < C
    slot_sorted = jnp.where(keep_sorted, se * C + rank, E * C)
    # unsort back to (token, choice) order
    slot = jnp.zeros((N,), slot_sorted.dtype).at[order].set(slot_sorted)
    keep = jnp.zeros((N,), bool).at[order].set(keep_sorted)
    return slot, keep


def _expert_mm(buffer, params):
    """buffer: (E, C, D) -> (E, C, D)."""
    g = jnp.einsum("ecd,edf->ecf", buffer, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buffer, params["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def _route(params, cfg, tokens):
    """tokens: (T, D) -> (gates (T,k) fp32, idx (T,k) int32, aux_loss)."""
    logits = tokens.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(logits, cfg.experts_top_k)
    gates = jax.nn.softmax(gates, axis=-1)
    # Switch-style load-balance auxiliary loss.
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _moe_local(params, cfg, tokens, C, ep_axes=None):
    """Capacity-dispatch MoE over a flat (T, D) token array."""
    T, D = tokens.shape
    E, k = cfg.n_experts, cfg.experts_top_k
    gates, idx, aux = _route(params, cfg, tokens)
    flat_e = idx.reshape(-1)
    slot, keep = _dispatch_indices(flat_e, E, C)
    tok_id = jnp.repeat(jnp.arange(T), k)

    buffer = jnp.zeros((E * C + 1, D), tokens.dtype)
    buffer = buffer.at[slot].set(tokens[tok_id], mode="drop")
    buffer = buffer[: E * C].reshape(E, C, D)
    if ep_axes is not None:
        buffer = jax.lax.with_sharding_constraint(buffer, ep_axes)
    out_buf = _expert_mm(buffer, params)
    if ep_axes is not None:
        out_buf = jax.lax.with_sharding_constraint(out_buf, ep_axes)

    flat_out = jnp.concatenate(
        [out_buf.reshape(E * C, D), jnp.zeros((1, D), out_buf.dtype)], 0)
    y_slots = flat_out[slot] * (gates.reshape(-1, 1).astype(out_buf.dtype)
                                * keep[:, None])
    y = jnp.zeros((T, D), tokens.dtype).at[tok_id].add(y_slots.astype(tokens.dtype))
    return y, aux


def _moe_shard_map(params, cfg, x, mesh):
    """Explicit expert-parallel path: tokens re-sharded over ("data","model"),
    all-to-all over "model" to expert owners, local expert matmul, reverse."""
    axis_names = mesh.axis_names
    model_ax = "model"
    data_axes = tuple(a for a in axis_names if a != model_ax)
    E, k, D = cfg.n_experts, cfg.experts_top_k, cfg.d_model
    m = mesh.shape[model_ax]
    E_l = E // m

    B, S, _ = x.shape

    def local_fn(router, w_gate, w_up, w_down, xs):
        # xs: (B_l, S, D) local tokens (also split over model axis)
        tokens = xs.reshape(-1, D)
        T_l = tokens.shape[0]
        C_l = _capacity(T_l, k, E, cfg.capacity_factor)
        p_local = {"router": router, "w_gate": w_gate, "w_up": w_up,
                   "w_down": w_down}
        gates, idx, aux = _route(p_local, cfg, tokens)
        flat_e = idx.reshape(-1)
        slot, keep = _dispatch_indices(flat_e, E, C_l)
        tok_id = jnp.repeat(jnp.arange(T_l), k)
        buf = jnp.zeros((E * C_l + 1, D), tokens.dtype)
        buf = buf.at[slot].set(tokens[tok_id], mode="drop")
        buf = buf[: E * C_l].reshape(m, E_l, C_l, D)
        # send expert groups to their owners
        recv = jax.lax.all_to_all(buf, model_ax, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (m, E_l, C_l, D) — m source shards' buffers for MY experts
        recv = jnp.moveaxis(recv, 0, 1).reshape(E_l, m * C_l, D)
        pl = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        out = _expert_mm(recv, pl)
        out = jnp.moveaxis(out.reshape(E_l, m, C_l, D), 1, 0)
        back = jax.lax.all_to_all(out, model_ax, split_axis=0, concat_axis=0,
                                  tiled=False)
        flat_out = jnp.concatenate(
            [back.reshape(E * C_l, D), jnp.zeros((1, D), back.dtype)], 0)
        y_slots = flat_out[slot] * (gates.reshape(-1, 1).astype(back.dtype)
                                    * keep[:, None])
        y = jnp.zeros((T_l, D), tokens.dtype).at[tok_id].add(
            y_slots.astype(tokens.dtype))
        return y.reshape(xs.shape), aux

    # tokens split over data axes on batch AND over model axis on sequence.
    in_specs = (P(), P(model_ax, None, None), P(model_ax, None, None),
                P(model_ax, None, None), P(data_axes, model_ax, None))
    out_specs = (P(data_axes, model_ax, None), P(data_axes, model_ax))

    def wrapper(router, wg, wu, wd, xs):
        y, aux = local_fn(router, wg, wu, wd, xs)
        return y, jnp.full((1, 1), aux)

    y, aux = jax.shard_map(wrapper, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)(
        params["router"], params["w_gate"], params["w_up"],
        params["w_down"], x)
    return y, jnp.mean(aux)


def moe_ffn(params, cfg, x, mesh=None, ep_axes=None):
    """x: (B, S, D) -> (y, aux_loss)."""
    B, S, D = x.shape
    if cfg.moe_shard_map and mesh is not None and "model" in mesh.axis_names \
            and mesh.shape["model"] > 1 and cfg.n_experts % mesh.shape["model"] == 0:
        y, aux = _moe_shard_map(params, cfg, x, mesh)
    else:
        tokens = x.reshape(-1, D)
        C = _capacity(tokens.shape[0], cfg.experts_top_k, cfg.n_experts,
                      cfg.capacity_factor)
        y, aux = _moe_local(params, cfg, tokens, C, ep_axes=ep_axes)
        y = y.reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x)
    return y, aux
