"""Mixture-of-Experts layer: top-k routing with sort-based dispatch.

Design (DESIGN.md §6): activations entering an MoE layer are replicated
across the ``model`` axis (the TP convention after an all-reduced mixer), so
*no all-to-all is needed for dispatch* — each model shard owns E/M experts,
selects the tokens routed to them with a local gather, runs its experts, and
the combine is a scatter-add followed by the same ``psum`` over ``model``
that a TP FFN would issue anyway.  Dispatch/combine are data movement
(gather/scatter), not einsums against one-hot masks, so HLO FLOPs stay
honest (the classic (tokens × E × C) dispatch einsum inflates compute by
orders of magnitude and would poison the roofline's MODEL/HLO ratio).

Routing is top-k softmax with optional renormalisation; per-expert capacity
C = ceil(T·k/E · capacity_factor) (tokens beyond capacity drop to the
residual path, standard practice).  A load-balancing auxiliary loss is
returned for training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ArchConfig, MoECfg
from .layers import init_dense, init_mlp, mlp

__all__ = ["init_moe", "moe_layer"]


def init_moe(key, cfg: ArchConfig) -> dict:
    m = cfg.moe
    d, dt = cfg.d_model, cfg.pdtype
    ks = jax.random.split(key, 4)
    ekeys = jax.random.split(ks[0], m.n_experts)
    scale = 1.0 / jnp.sqrt(d)

    def one_expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wi_gate": (jax.random.normal(k1, (d, m.d_ff_expert), jnp.float32) * scale).astype(dt),
            "wi_up": (jax.random.normal(k2, (d, m.d_ff_expert), jnp.float32) * scale).astype(dt),
            "wo": (jax.random.normal(k3, (m.d_ff_expert, d), jnp.float32) * scale).astype(dt),
        }

    p = {
        "router": init_dense(ks[1], d, m.n_experts, jnp.float32),
        "experts": jax.vmap(one_expert)(ekeys),  # stacked (E, ...) leaves
    }
    if m.n_shared:
        p["shared"] = init_mlp(ks[2], d, m.d_ff_shared or m.d_ff_expert * m.n_shared,
                               dt, cfg.mlp_act)
    return p


def _route(x2d, router_w, m: MoECfg):
    """x2d (T, d) → (top-k expert ids (T,k), gates (T,k), router probs (T,E))."""
    logits = jnp.dot(x2d.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    if m.router_renorm:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_i, top_p, probs


def _expert_ffn(buf, experts, act: str):
    """buf (E_loc, C, d) through per-expert gated MLPs (batched matmul)."""

    def one(xe, pe):
        return mlp(xe, pe, act)

    return jax.vmap(one)(buf, experts)


def _moe_local(x2d, p, m: MoECfg, act: str, e_start, E_loc: int, capacity: int):
    """Dispatch/compute/combine for the experts [e_start, e_start+E_loc).

    Runs identically on every model shard (with different ``e_start``); the
    caller sums the partial outputs (psum over 'model' under shard_map, or
    a plain sum of one shard when unsharded).
    """
    T, d = x2d.shape
    k = m.top_k
    top_i, top_g, probs = _route(x2d, p["router"], m)

    flat_e = top_i.reshape(-1)                      # (T·k,) expert ids
    flat_t = jnp.repeat(jnp.arange(T), k)           # token of each assignment
    flat_g = top_g.reshape(-1).astype(x2d.dtype)

    # rank of each assignment within its expert (stable → earlier tokens win)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.bincount(flat_e, length=m.n_experts)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(T * k) - starts[flat_e[order]]
    rank = jnp.zeros((T * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    local = (flat_e >= e_start) & (flat_e < e_start + E_loc)
    keep = local & (rank < capacity)
    slot = jnp.where(keep, (flat_e - e_start) * capacity + rank, E_loc * capacity)

    buf = jnp.zeros((E_loc * capacity + 1, d), x2d.dtype).at[slot].set(
        jnp.where(keep[:, None], x2d[flat_t], 0.0)
    )[: E_loc * capacity]
    h = _expert_ffn(buf.reshape(E_loc, capacity, d), p["experts"], act)
    h = h.reshape(E_loc * capacity, d)

    gathered = jnp.where(keep[:, None], h[jnp.minimum(slot, E_loc * capacity - 1)], 0.0)
    y = jnp.zeros((T, d), x2d.dtype).at[flat_t].add(gathered * flat_g[:, None])

    # load-balance aux loss (Switch-style): E · Σ_e f_e · P_e
    f = jnp.bincount(flat_e, length=m.n_experts).astype(jnp.float32) / (T * k)
    P = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(f * P)
    return y, aux


def moe_layer(x, p, cfg: ArchConfig, *, mesh=None) -> tuple[jax.Array, jax.Array]:
    """x (B, S, d) → (y, aux_loss).  EP over 'model' when a mesh is given."""
    m = cfg.moe
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)

    if mesh is not None and m.sharding == "ep" and "model" in mesh.shape:
        M = mesh.shape["model"]
        E_pad = ((m.n_experts + M - 1) // M) * M
        E_loc = E_pad // M

        from jax.sharding import PartitionSpec as P

        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        dp = 1
        for a in batch_axes:
            dp *= mesh.shape[a]
        if (B * S) % max(dp, 1):
            batch_axes = ()  # decode with tiny batches: replicate tokens
            dp = 1
        T_loc = (B * S) // dp
        capacity = max(8, int(T_loc * m.top_k * m.capacity_factor / m.n_experts))

        def body(x_loc, router_w, experts):
            me = jax.lax.axis_index("model")
            pp = {"router": router_w, "experts": experts}
            y, aux = _moe_local(
                x_loc, pp, m, cfg.mlp_act, me * E_loc, E_loc, capacity
            )
            y = jax.lax.psum(y, "model")
            aux = jax.lax.pmean(aux, "model")
            if batch_axes:
                aux = jax.lax.pmean(aux, batch_axes)
            return y, aux

        experts = p["experts"]
        if E_pad != m.n_experts:  # pad expert stack so E divides the axis
            pad = E_pad - m.n_experts
            experts = jax.tree.map(
                lambda w: jnp.concatenate([w, jnp.zeros((pad,) + w.shape[1:], w.dtype)]), experts
            )
        y2d, aux = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(batch_axes if batch_axes else None, None), P(), P("model")),
            out_specs=(P(batch_axes if batch_axes else None, None), P()),
            check_vma=False,
        )(x2d, p["router"], experts)
    else:
        capacity = max(4, int(B * S * m.top_k * m.capacity_factor / m.n_experts))
        y2d, aux = _moe_local(x2d, p, m, cfg.mlp_act, 0, m.n_experts, capacity)

    y = y2d.reshape(B, S, d)
    if m.n_shared:
        y = y + mlp(x, p["shared"], cfg.mlp_act)
    return y, aux
