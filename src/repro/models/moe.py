"""Mixture-of-Experts FFN.

Two execution paths sharing one router:

* ``dense``  — every expert runs on every token, combined with top-k weights.
  O(E/topk) FLOP overhead; used only for smoke tests and as the oracle.
* ``ep``     — production path. Experts are sharded over the ``data`` mesh axis
  (storage and compute) and the expert FFN dim over ``model``. Token dispatch is
  a fixed-capacity all_to_all over ``data`` inside ``shard_map``; the combine
  rides the same ``psum`` over ``model`` a dense TP FFN would need. See
  DESIGN.md §4 (EP).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common import ParamDecl, active_mesh, logical_shard
from repro.configs.base import ModelConfig


def moe_decls(cfg: ModelConfig) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    decls = {
        "router": ParamDecl((d, e), ("p_embed", "p_none"), init="scaled",
                            dtype=jnp.float32),
        "w_gate": ParamDecl((e, d, ff), ("p_experts", "p_expert_embed", "p_mlp"), init="scaled"),
        "w_up": ParamDecl((e, d, ff), ("p_experts", "p_expert_embed", "p_mlp"), init="scaled"),
        "w_down": ParamDecl((e, ff, d), ("p_experts", "p_mlp", "p_expert_embed"), init="scaled"),
    }
    if cfg.n_shared_experts:
        sf = ff * cfg.n_shared_experts
        decls["shared"] = {
            "w_gate": ParamDecl((d, sf), ("p_embed", "p_mlp"), init="scaled"),
            "w_up": ParamDecl((d, sf), ("p_embed", "p_mlp"), init="scaled"),
            "w_down": ParamDecl((sf, d), ("p_mlp", "p_embed"), init="scaled"),
        }
    return decls


def _router_topk(x: jax.Array, w_router: jax.Array, top_k: int):
    """x: (T, d) -> (weights (T,k) fp32, idx (T,k) int32, logits for aux)."""
    logits = (x.astype(jnp.float32) @ w_router)  # (T, E)
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(top_vals, axis=-1)
    return weights, top_idx, logits


def _swiglu_grouped(h: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """h: (E_loc, C, d) grouped tokens; weights (E_loc, d, ff)/(E_loc, ff, d)."""
    a = jnp.einsum("ecd,edf->ecf", h, w_gate)
    b = jnp.einsum("ecd,edf->ecf", h, w_up)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(a) * b, w_down)


def moe_dense(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """Oracle path: all experts on all tokens; exact for any capacity."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    weights, idx, _ = _router_topk(xt, params["router"], cfg.top_k)
    full = jnp.zeros((t, cfg.n_experts), jnp.float32)
    full = full.at[jnp.arange(t)[:, None], idx].set(weights)
    # (E, T, d) all-expert outputs
    h = jnp.einsum("td,edf->etf", xt, params["w_gate"])
    u = jnp.einsum("td,edf->etf", xt, params["w_up"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, params["w_down"])
    out = jnp.einsum("etd,te->td", y.astype(jnp.float32), full)
    return out.reshape(b, s, d).astype(x.dtype)


def _moe_local(
    cfg: ModelConfig,
    x_loc: jax.Array,        # (T_loc, d) tokens local to this data shard
    router_w: jax.Array,     # (d, E) replicated
    w_gate: jax.Array,       # (E_loc, d, ff_loc)
    w_up: jax.Array,
    w_down: jax.Array,       # (E_loc, ff_loc, d)
    *,
    n_dest: int,
    axis_data: Optional[str],
    axis_model: Optional[str],
) -> jax.Array:
    """Per-shard MoE body (runs under shard_map, or standalone when axes None)."""
    t_loc, d = x_loc.shape
    e = cfg.n_experts
    e_loc = e // n_dest
    k = cfg.top_k
    # per-expert capacity of the send buffer
    cap = max(4, int(-(-t_loc * k * cfg.capacity_factor // e)))

    weights, idx, _ = _router_topk(x_loc, router_w, k)            # (T,k)
    flat_e = idx.reshape(-1)                                      # (T*k,)
    # slot within each expert's capacity bucket, computed via running counts
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)           # (T*k, E)
    slot = (jnp.cumsum(onehot, axis=0) - 1) * onehot              # rank within expert
    slot = slot.sum(axis=-1)                                      # (T*k,)
    keep = slot < cap                                             # capacity drop mask

    send = jnp.zeros((e, cap, d), x_loc.dtype)
    src_token = jnp.repeat(jnp.arange(t_loc), k)
    # dropped copies get an out-of-bounds slot -> discarded by mode="drop"
    send = send.at[flat_e, jnp.where(keep, slot, cap)].set(
        x_loc[src_token], mode="drop"
    )

    if axis_data is not None and n_dest > 1:
        # (E, cap, d) -> (n_dest, E_loc, cap, d) -> exchange over data axis
        buf = send.reshape(n_dest, e_loc, cap, d)
        buf = jax.lax.all_to_all(buf, axis_data, split_axis=0, concat_axis=0,
                                 tiled=True)                      # (n_src*E_loc, cap, d)
        recv = buf.reshape(n_dest, e_loc, cap, d)
    else:
        recv = send.reshape(1, e_loc, cap, d) if n_dest == 1 else send.reshape(
            n_dest, e_loc, cap, d)

    # group by local expert: (E_loc, n_src*cap, d)
    grouped = jnp.moveaxis(recv, 0, 1).reshape(e_loc, -1, d)
    y = _swiglu_grouped(grouped, w_gate, w_up, w_down)            # (E_loc, n_src*cap, d)
    # ff_loc partials are summed over 'model' AFTER the combine below: psum
    # commutes with the (linear) return-route + weighted combine, and the
    # combined (T, d) buffer is top_k x smaller than the expert buffer
    # (EXPERIMENTS.md §Perf iteration 4)

    # route results back to sources
    y = jnp.moveaxis(y.reshape(e_loc, n_dest, cap, d), 1, 0)      # (n_dest, E_loc, cap, d)
    if axis_data is not None and n_dest > 1:
        y = jax.lax.all_to_all(y.reshape(n_dest * e_loc, cap, d), axis_data,
                               split_axis=0, concat_axis=0, tiled=True)
    y = y.reshape(e, cap, d)

    gathered = y[flat_e, jnp.clip(slot, 0, cap - 1)]              # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    w_flat = weights.reshape(-1)[:, None].astype(jnp.float32)
    out = jnp.zeros((t_loc, d), jnp.float32)
    out = out.at[src_token].add(gathered.astype(jnp.float32) * w_flat)
    out = out.astype(x_loc.dtype)
    if axis_model is not None:
        out = jax.lax.psum(out, axis_model)   # deferred TP reduction
    return out


def moe_ep(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """Expert-parallel path over the active mesh (falls back to local body)."""
    b, s, d = x.shape
    mesh = active_mesh()
    xt = x.reshape(b * s, d)
    if mesh is None or "data" not in mesh.axis_names or mesh.shape["data"] == 1:
        y = _moe_local(cfg, xt, params["router"], params["w_gate"], params["w_up"],
                       params["w_down"], n_dest=1, axis_data=None, axis_model=None)
        return y.reshape(b, s, d)

    n_dest = mesh.shape["data"]
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    body = lambda xt_, rw, wg, wu, wd: _moe_local(
        cfg, xt_, rw, wg, wu, wd, n_dest=n_dest, axis_data="data", axis_model="model"
    )
    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp, None),                 # tokens: sharded over data(+pod)
            P(None, None),               # router: replicated
            P("data", None, "model"),    # experts: EP over data, TP over model
            P("data", None, "model"),
            P("data", "model", None),
        ),
        out_specs=P(dp, None),
        check_vma=False,
    )(xt, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return y.reshape(b, s, d)


def moe_block(cfg: ModelConfig, params: dict, x: jax.Array, *,
              impl: str = "auto") -> jax.Array:
    """Routed experts (+ optional shared expert)."""
    if impl == "auto":
        impl = "ep" if active_mesh() is not None else "dense"
    y = moe_ep(cfg, params, x) if impl == "ep" else moe_dense(cfg, params, x)
    if cfg.n_shared_experts:
        sp = params["shared"]
        h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
        h = logical_shard(h, "batch", "seq", "mlp_act")
        y = y + h @ sp["w_down"]
    return logical_shard(y, "batch", "seq", "embed")
