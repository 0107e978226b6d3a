"""Mixture-of-Experts FFN: top-k routing, sort-based grouped GEMM
(`lax.ragged_dot`), and explicit expert/tensor parallelism via shard_map.

Parallelism policy (DESIGN.md Sec. 3.3):
  * E >= model-axis size  -> **EP**: experts sharded over `model`; tokens
    (replicated across `model` under TP) are selected per shard by a
    stable sort on expert id with a per-shard capacity, computed with the
    shard's local experts, and combined with a psum — the same psum a
    dense TP MLP needs, so EP adds no extra collective traffic.
  * E <  model-axis size  -> **TP**: every shard holds all experts' d_ff
    slice; sorted grouped GEMM over the slice, psum of the down-proj.

Expert hotness for memos: the router's per-expert token counts are exactly
the paper's bank-utilization histogram (Algorithm 1); they are returned to
the caller so SysMon can track expert pages and the placement engine can
rebalance expert->device maps (bank rebalancing) and tier cold experts.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class MoEParams(NamedTuple):
    w_router: jnp.ndarray   # [d, E]
    w_gate: jnp.ndarray     # [E, d, ff]
    w_up: jnp.ndarray       # [E, d, ff]
    w_down: jnp.ndarray     # [E, ff, d]


def init_moe_params(key, d_model: int, n_experts: int, d_ff: int,
                    dtype=jnp.float32) -> MoEParams:
    k0, k1, k2, k3 = jax.random.split(key, 4)
    s = d_model ** -0.5
    return MoEParams(
        w_router=(jax.random.normal(k0, (d_model, n_experts)) * s).astype(dtype),
        w_gate=(jax.random.normal(k1, (n_experts, d_model, d_ff)) * s).astype(dtype),
        w_up=(jax.random.normal(k2, (n_experts, d_model, d_ff)) * s).astype(dtype),
        w_down=(jax.random.normal(k3, (n_experts, d_ff, d_model)) * s).astype(dtype),
    )


def route(x_flat: jnp.ndarray, w_router: jnp.ndarray, top_k: int,
          *, norm_topk: bool = True, softmax_before_topk: bool = True):
    """Top-k routing.  Returns (weights [T,k] f32, idx [T,k] i32,
    probs [T,E] f32, counts [E] i32 — the expert hotness histogram)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    if softmax_before_topk:          # olmoe style
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, top_k)
    else:                            # mixtral style: softmax over the top-k
        top_logits, idx = jax.lax.top_k(logits, top_k)
        w = jax.nn.softmax(top_logits, axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
    if norm_topk:
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    counts = jnp.zeros(w_router.shape[1], jnp.int32).at[idx.reshape(-1)].add(1)
    return w, idx.astype(jnp.int32), probs, counts


def aux_load_balance_loss(probs: jnp.ndarray, idx: jnp.ndarray,
                          n_experts: int) -> jnp.ndarray:
    """Switch-style load-balancing aux loss: E * sum_e f_e * P_e."""
    T = probs.shape[0]
    f = jnp.zeros(n_experts, jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = f / jnp.maximum(T * idx.shape[-1], 1)
    pbar = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * pbar)


def _grouped_ffn(xg: jnp.ndarray, gs: jnp.ndarray, w_gate, w_up, w_down,
                 act=jax.nn.silu) -> jnp.ndarray:
    """Grouped SwiGLU over expert-sorted rows via ragged_dot."""
    g = jax.lax.ragged_dot(xg, w_gate, gs, preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xg, w_up, gs, preferred_element_type=jnp.float32)
    h = (act(g) * u).astype(xg.dtype)
    y = jax.lax.ragged_dot(h, w_down, gs, preferred_element_type=jnp.float32)
    return y


def moe_sorted_local(x_flat: jnp.ndarray, p: MoEParams, top_k: int,
                     *, softmax_before_topk: bool = True,
                     norm_topk: bool = True, act=jax.nn.silu):
    """Single-shard sort-based MoE over all experts (no dropping).

    Used standalone on one device and as the per-shard body of the TP path
    (where p.w_gate/up/down are the shard's d_ff slice)."""
    T, d = x_flat.shape
    E = p.w_router.shape[1]
    w, idx, probs, counts = route(x_flat, p.w_router, top_k,
                                  norm_topk=norm_topk,
                                  softmax_before_topk=softmax_before_topk)
    flat_e = idx.reshape(-1)                                 # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    tok = order // top_k
    xg = x_flat[tok]                                          # [T*k, d]
    gs = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    y = _grouped_ffn(xg, gs, p.w_gate, p.w_up, p.w_down, act)  # [T*k, d] f32
    gatew = w.reshape(-1)[order]
    y = y * gatew[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(y)
    return out.astype(x_flat.dtype), probs, idx, counts


def _ep_shard_body(x_flat, p: MoEParams, top_k, n_ep, capacity,
                   model_axis, softmax_before_topk, norm_topk, act):
    """Per-shard EP body (runs under shard_map; x replicated over `model`)."""
    T, d = x_flat.shape
    E = p.w_router.shape[1]           # local view: w_router replicated
    E_local = E // n_ep
    m = jax.lax.axis_index(model_axis) % n_ep

    w, idx, probs, counts = route(x_flat, p.w_router, top_k,
                                  norm_topk=norm_topk,
                                  softmax_before_topk=softmax_before_topk)
    flat_e = idx.reshape(-1)                                   # [T*k]
    local_e = flat_e - m * E_local
    mine = (local_e >= 0) & (local_e < E_local)
    key = jnp.where(mine, local_e, E_local)                    # not-mine last
    order = jnp.argsort(key, stable=True)[:capacity]           # mine first
    valid = key[order] < E_local
    tok = order // top_k
    xg = x_flat[tok] * valid[:, None].astype(x_flat.dtype)

    # group sizes over local experts; invalid tail rides in the last group
    cnt = jnp.zeros(E_local + 1, jnp.int32).at[key[order]].add(1)
    gs = cnt[:E_local].at[E_local - 1].add(cnt[E_local])

    y = _grouped_ffn(xg, gs, p.w_gate, p.w_up, p.w_down, act)   # local experts
    gatew = w.reshape(-1)[order] * valid
    y = y * gatew[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(y)
    out = jax.lax.psum(out, model_axis)
    return out.astype(x_flat.dtype), probs, idx, counts


def _tp_shard_body(x_flat, p: MoEParams, top_k, model_axis,
                   softmax_before_topk, norm_topk, act):
    """Per-shard TP body: all experts present, d_ff sliced over `model`."""
    out, probs, idx, counts = moe_sorted_local(
        x_flat, p, top_k, softmax_before_topk=softmax_before_topk,
        norm_topk=norm_topk, act=act)
    out = jax.lax.psum(out.astype(jnp.float32), model_axis).astype(x_flat.dtype)
    return out, probs, idx, counts


def moe_apply(x: jnp.ndarray, p: MoEParams, *, top_k: int,
              mesh: jax.sharding.Mesh | None = None,
              dp_axes: tuple[str, ...] = ("data",), model_axis: str = "model",
              capacity_factor: float = 1.25,
              softmax_before_topk: bool = True, norm_topk: bool = True,
              act=jax.nn.silu):
    """MoE FFN over x [B, S, d].  Returns (y [B,S,d], aux) where aux carries
    (router probs, topk idx, expert counts) for the aux loss and SysMon.

    With a mesh, runs under shard_map with EP when E >= |model| else TP.
    """
    B, S, d = x.shape
    E = p.w_router.shape[1]
    xf = x.reshape(B * S, d)

    if mesh is None:
        y, probs, idx, counts = moe_sorted_local(
            xf, p, top_k, softmax_before_topk=softmax_before_topk,
            norm_topk=norm_topk, act=act)
        return y.reshape(B, S, d), (probs, idx, counts)

    import math
    n_model = mesh.shape[model_axis]
    n_dp = math.prod(mesh.shape[a] for a in dp_axes)
    # tiny decode batches (long-context, B=1) replicate over the data axes
    dp_replicated = (B * S) % n_dp != 0
    dp_spec = P(None) if dp_replicated else P(dp_axes)
    use_ep = E >= n_model and E % n_model == 0

    if use_ep:
        n_ep = n_model
        T_local = (B * S) if dp_replicated else (B * S) // n_dp
        capacity = int(T_local * top_k / n_ep * capacity_factor)
        capacity = max(8, -(-capacity // 8) * 8)  # round up to 8
        capacity = min(capacity, T_local * top_k)
        pspec = MoEParams(P(), P(model_axis, None, None),
                          P(model_axis, None, None), P(model_axis, None, None))
        body = partial(_ep_shard_body, top_k=top_k, n_ep=n_ep,
                       capacity=capacity, model_axis=model_axis,
                       softmax_before_topk=softmax_before_topk,
                       norm_topk=norm_topk, act=act)
    else:
        pspec = MoEParams(P(), P(None, None, model_axis),
                          P(None, None, model_axis), P(None, model_axis, None))
        body = partial(_tp_shard_body, top_k=top_k, model_axis=model_axis,
                       softmax_before_topk=softmax_before_topk,
                       norm_topk=norm_topk, act=act)

    out_specs = (dp_spec, dp_spec, dp_spec, P())  # y, probs, idx, counts

    def wrapped(xx, pp):
        y, probs, idx, counts = body(xx, pp)
        # expert histogram: global sum (SysMon's bank-frequency table)
        if not dp_replicated:
            counts = jax.lax.psum(counts, dp_axes)
        return y, probs, idx, counts

    fn = jax.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=(dp_spec, pspec),
        out_specs=out_specs,
        check_vma=False,
    )
    y, probs, idx, counts = fn(xf, p)
    return y.reshape(B, S, d), (probs, idx, counts)
