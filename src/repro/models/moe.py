"""Mixture-of-Experts block with capacity-based top-k routing.

Expert parallelism: experts are sharded over the ``tp`` (model) mesh axis;
tokens stay sharded over the batch axes and are *replicated* over the model
axis inside the block. Each model rank computes only its local experts'
contribution (gather → expert FFN → scatter-add) and a single psum over the
model axis combines routed + shared-expert partial sums. This avoids
all-to-all dispatch entirely (the psum moves (T, d) activations — for top-k ≥ 4
this is usually cheaper on ICI than two all-to-alls of the dispatched
(T·k/E_loc, d) plus load imbalance; see EXPERIMENTS.md §Perf).

Routing is GShard-style with a static per-expert capacity
``C = ceil(T_local · top_k / E · capacity_factor)``; overflow tokens are
dropped (their combine weight is 0) — load-balance aux loss keeps the router
honest. Padded experts (e.g. qwen2-moe 60→64) are masked to −inf in the
router logits.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import (
    ParamSpec,
    current_ctx,
    dense_spec,
)


def moe_defs(cfg):
    m = cfg.moe
    d, e, f = cfg.d_model, m.num_experts, m.d_ff_expert
    defs = {
        "router": ParamSpec((d, e), (None, None), scale=d ** -0.5,
                            dtype=jnp.float32),
        "w_in": ParamSpec((e, d, 2 * f), ("tp", "fsdp", None), scale=d ** -0.5),
        "w_out": ParamSpec((e, f, d), ("tp", None, "fsdp"), scale=f ** -0.5),
    }
    if m.num_shared_experts:
        fs = m.d_ff_shared
        defs["w_sh_gate"] = dense_spec(d, fs)
        defs["w_sh_up"] = dense_spec(d, fs)
        defs["w_sh_down"] = dense_spec(fs, d, logical=("tp", "fsdp"))
    return defs


def _route(x2d, router, moe_cfg):
    """Top-k routing. x2d: (T, d) -> (topi, weights (T,k), aux scalar)."""
    e, e_real, k = moe_cfg.num_experts, moe_cfg.num_experts_unpadded, moe_cfg.top_k
    logits = x2d.astype(jnp.float32) @ router
    if e_real < e:
        logits = jnp.where(jnp.arange(e) < e_real, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # Switch/GShard load-balance loss: E * sum_e f_e * p_e
    assign = jnp.zeros_like(probs).at[
        jnp.arange(x2d.shape[0])[:, None], topi].set(1.0)
    f_e = assign.mean(0)                      # fraction routed to e (×k)
    p_e = probs.mean(0)
    aux = e_real * jnp.sum(f_e * p_e) / k
    return topi, topv, aux


def _dispatch_tables(topi, topv, e: int, capacity: int):
    """Build (E, C) token-index / combine-weight / validity tables."""
    t, k = topi.shape
    flat_e = topi.reshape(-1)                                  # (T*k,)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - 1
    mypos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    tok_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    valid = mypos < capacity
    tok_tbl = jnp.zeros((e, capacity), jnp.int32).at[flat_e, mypos].set(
        tok_ids, mode="drop")
    val_tbl = jnp.zeros((e, capacity), bool).at[flat_e, mypos].set(
        valid, mode="drop")
    cmb_tbl = jnp.zeros((e, capacity), jnp.float32).at[flat_e, mypos].set(
        jnp.where(valid, topv.reshape(-1), 0.0), mode="drop")
    return tok_tbl, cmb_tbl, val_tbl


def _moe_device(x, p, cfg, e_start, e_local: int, tp_axis: Optional[str]):
    """Per-device MoE computation (runs inside shard_map, or standalone when
    there is no mesh). x: (b, S, d) local."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    capacity = max(1, math.ceil(t * m.top_k / m.num_experts * m.capacity_factor))

    topi, topv, aux = _route(x2d, p["router"], m)
    tok_tbl, cmb_tbl, val_tbl = _dispatch_tables(topi, topv, m.num_experts,
                                                 capacity)
    tok_loc = jax.lax.dynamic_slice_in_dim(tok_tbl, e_start, e_local, 0)
    cmb_loc = jax.lax.dynamic_slice_in_dim(cmb_tbl, e_start, e_local, 0)
    val_loc = jax.lax.dynamic_slice_in_dim(val_tbl, e_start, e_local, 0)

    w_in = p["w_in"] if p["w_in"].shape[0] == e_local else \
        jax.lax.dynamic_slice_in_dim(p["w_in"], e_start, e_local, 0)
    w_out = p["w_out"] if p["w_out"].shape[0] == e_local else \
        jax.lax.dynamic_slice_in_dim(p["w_out"], e_start, e_local, 0)

    xg = jnp.take(x2d, tok_loc.reshape(-1), axis=0).reshape(e_local, capacity, d)
    gu = jnp.einsum("ecd,edf->ecf", xg, w_in)
    gate, up = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, w_out)
    out = out * (cmb_loc * val_loc)[..., None].astype(out.dtype)
    y = jnp.zeros((t, d), out.dtype).at[tok_loc.reshape(-1)].add(
        out.reshape(-1, d))

    if m.num_shared_experts:
        # shared experts: plain TP over the ff dim (partial sums join the psum)
        hs = jax.nn.silu(x2d @ p["w_sh_gate"]) * (x2d @ p["w_sh_up"])
        y = y + hs @ p["w_sh_down"]

    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y.reshape(b, s, d), aux


def _moe_device_a2a(x, p, cfg, e_local: int, tp_axis: str):
    """GShard-style expert parallelism (runs inside shard_map): tokens are
    sharded over the model axis; dispatch buffers travel to the expert
    owners via all-to-all and return the same way. x: (b, s_loc, d)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    capacity = max(1, math.ceil(t * m.top_k / m.num_experts
                                * m.capacity_factor))

    x2d = x.reshape(t, d)
    topi, topv, aux = _route(x2d, p["router"], m)
    tok_tbl, cmb_tbl, val_tbl = _dispatch_tables(topi, topv, m.num_experts,
                                                 capacity)
    xg = jnp.take(x2d, tok_tbl.reshape(-1), axis=0).reshape(
        m.num_experts, capacity, d)
    xg = xg * val_tbl[..., None].astype(xg.dtype)
    # dispatch: (E, C, d) -> (E/tp, tp*C, d) on the owning rank
    xr = jax.lax.all_to_all(xg, tp_axis, split_axis=0, concat_axis=1,
                            tiled=True)
    gu = jnp.einsum("ecd,edf->ecf", xr, p["w_in"])
    gate, up = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, p["w_out"])
    # return trip: (E/tp, tp*C, d) -> (E, C, d)
    out = jax.lax.all_to_all(out, tp_axis, split_axis=1, concat_axis=0,
                             tiled=True)
    out = out * (cmb_tbl * val_tbl)[..., None].astype(out.dtype)
    y = jnp.zeros((t, d), out.dtype).at[tok_tbl.reshape(-1)].add(
        out.reshape(-1, d))

    if m.num_shared_experts:
        # tokens are rank-disjoint here: shared experts run with FULL
        # (replicated) weights — no psum
        hs = jax.nn.silu(x2d @ p["w_sh_gate"]) * (x2d @ p["w_sh_up"])
        y = y + hs @ p["w_sh_down"]
    return y.reshape(b, s, d), aux


def moe_block(p, cfg, x) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN. x: (B, S, d) (global). Returns (y, aux_loss)."""
    ctx = current_ctx()
    m = cfg.moe
    if ctx.mesh is None:
        y, aux = _moe_device(x, p, cfg, 0, m.num_experts, None)
        return y, aux

    mesh = ctx.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_axes = tuple(a for a in ctx.rules["tp"] if a in sizes)
    dp_axes = tuple(a for a in ctx.rules["batch"] if a in sizes)
    assert len(tp_axes) == 1, "MoE expert parallelism expects one model axis"
    tp_axis = tp_axes[0]
    tp = sizes[tp_axis]
    assert m.num_experts % tp == 0, (m.num_experts, tp)
    e_local = m.num_experts // tp

    bspec = dp_axes if x.shape[0] % math.prod(sizes[a] for a in dp_axes) == 0 \
        else None
    use_a2a = (m.parallelism == "alltoall" and x.shape[1] % tp == 0
               and x.shape[1] > 1)
    x_spec = P(bspec, tp_axis if use_a2a else None, None)
    p_specs = {
        "router": P(None, None),
        "w_in": P(tp_axis, None, None),
        "w_out": P(tp_axis, None, None),
    }
    if m.num_shared_experts:
        fs_ok = m.d_ff_shared % tp == 0 and not use_a2a
        p_specs["w_sh_gate"] = P(None, tp_axis if fs_ok else None)
        p_specs["w_sh_up"] = P(None, tp_axis if fs_ok else None)
        p_specs["w_sh_down"] = P(tp_axis if fs_ok else None, None)

    def fn(x_loc, p_loc):
        if use_a2a:
            y, aux = _moe_device_a2a(x_loc, p_loc, cfg, e_local, tp_axis)
        else:
            rank = jax.lax.axis_index(tp_axis)
            y, aux = _moe_device(x_loc, p_loc, cfg, rank * e_local, e_local,
                                 tp_axis)
        aux = jax.lax.pmean(aux, dp_axes + (tp_axis,))
        if bspec is None and dp_axes:
            # batch replicated over dp: outputs identical; average for safety
            y = jax.lax.pmean(y, dp_axes)
        return y, aux

    other = tuple(a for a in mesh.axis_names
                  if a not in dp_axes and a != tp_axis)
    if other:
        def fn_wrapped(x_loc, p_loc):
            y, aux = fn(x_loc, p_loc)
            return y, jax.lax.pmean(aux, other)
    else:
        fn_wrapped = fn

    y, aux = jax.shard_map(
        fn_wrapped, mesh=mesh,
        in_specs=(x_spec, p_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, {k: p[k] for k in p_specs})
    return y, aux
