"""Composable decoder covering all ten assigned architectures.

Layouts:
  * ``attn``   — [norm→attention→(post)norm] + [norm→MLP|MoE→(post)norm],
                 scanned over stacked layer params (single trace per arch);
  * ``mamba``  — Mamba-2 SSD blocks, scanned;
  * ``hybrid`` — Mamba-2 backbone + a *shared* attention block applied every
                 ``shared_attn_every`` layers (zamba2), via lax.cond inside
                 the scan (both branches traced once).

Train/prefill forward uses lax.scan over layers (small HLO, remat-wrapped);
prefill and decode use a python loop so heterogeneous per-layer caches
(local/global windows, shared-attn sites, SSM state) stay simple and the
cache updates alias in place.

Sharding intent is expressed with with_sharding_constraint at block
boundaries (Megatron-SP / context-parallel per parallel/sharding.py);
everything also runs unsharded (mi=None) for CPU tests.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel import sharding as sh
from . import attention as attn_mod
from . import layers, moe as moe_mod, ssm


# =============================================================================
# parameter initialization
# =============================================================================

def _attn_dict(p: attn_mod.AttnParams) -> dict:
    return {k: v for k, v in p._asdict().items() if v is not None}


def _attn_from_dict(d: dict) -> attn_mod.AttnParams:
    return attn_mod.AttnParams(
        wq=d["wq"], wk=d["wk"], wv=d["wv"], wo=d["wo"],
        bq=d.get("bq"), bk=d.get("bk"), bv=d.get("bv"),
        q_norm=d.get("q_norm"), k_norm=d.get("k_norm"))


def mamba_spec_of(cfg: ArchConfig) -> ssm.MambaSpec:
    return ssm.make_spec(cfg.d_model, expand=cfg.ssm_expand,
                         headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
                         chunk=cfg.chunk)


def _keys_per_layer(cfg: ArchConfig) -> int:
    if cfg.layout in ("mamba", "hybrid"):
        return 1
    if cfg.is_moe:
        return 2
    return 3 if cfg.mlp_kind == "gelu" else 4


def _init_blocks(cfg: ArchConfig, keys, dtype):
    """Builders for one layer's (and the shared block's) params, each
    drawing its random leaves from ``keys`` in order."""
    d = cfg.d_model

    def one_attn():
        return _attn_dict(attn_mod.init_attn_params(
            next(keys), d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm, dtype=dtype))

    def one_mlp(d_ff: int):
        s = d ** -0.5
        if cfg.mlp_kind == "gelu":
            return {"w_up": (jax.random.normal(next(keys), (d, d_ff)) * s
                             ).astype(dtype),
                    "w_down": (jax.random.normal(next(keys), (d_ff, d))
                               * d_ff ** -0.5).astype(dtype)}
        return {"w_gate": (jax.random.normal(next(keys), (d, d_ff)) * s
                           ).astype(dtype),
                "w_up": (jax.random.normal(next(keys), (d, d_ff)) * s
                         ).astype(dtype),
                "w_down": (jax.random.normal(next(keys), (d_ff, d))
                           * d_ff ** -0.5).astype(dtype)}

    ln = lambda: (jnp.zeros((d,), dtype) if cfg.gemma_norm  # noqa: E731
                  else jnp.ones((d,), dtype))
    return one_attn, one_mlp, ln


def _init_layer(cfg: ArchConfig, layer_keys, dtype) -> dict:
    """One layer's params from its ``_keys_per_layer(cfg)`` keys."""
    keys = iter(layer_keys)
    one_attn, one_mlp, ln = _init_blocks(cfg, keys, dtype)
    if cfg.layout in ("mamba", "hybrid"):
        return {"ln": ln(), "mamba": ssm.init_mamba_params(
            next(keys), mamba_spec_of(cfg), dtype)._asdict()}
    lp: dict = {"ln1": ln(), "ln2": ln(), "attn": one_attn()}
    if cfg.is_moe:
        lp["moe"] = moe_mod.init_moe_params(
            next(keys), cfg.d_model, cfg.n_experts,
            cfg.expert_d_ff or cfg.d_ff, dtype)._asdict()
    else:
        lp["mlp"] = one_mlp(cfg.d_ff)
    if cfg.gemma_norm:
        lp["ln1_post"] = ln()
        lp["ln2_post"] = ln()
    return lp


@partial(jax.jit, donate_argnums=(0,))
def _put_layer(stack, layer, lp):
    return jax.tree.map(lambda s, x: s.at[layer].set(x), stack, lp)


def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> dict:
    """Random params from ``key``.  The layer stack is allocated once and
    filled one layer at a time (each layer drawn, then written into the
    donated stack), so peak memory stays near one copy of the weights in
    ``dtype`` — the full-width bf16 model fits one chip."""
    L = cfg.n_layers
    keys = jax.random.split(key, 8 * L + 16)
    kpl = _keys_per_layer(cfg)
    init_one = partial(_init_layer, cfg, dtype=dtype)
    shapes = jax.eval_shape(init_one, keys[:kpl])
    stack = jax.tree.map(lambda a: jnp.zeros((L, *a.shape), a.dtype), shapes)
    for l in range(L):
        stack = _put_layer(stack, l, init_one(keys[l * kpl:(l + 1) * kpl]))

    rest = iter(keys[L * kpl:])
    one_attn, one_mlp, ln = _init_blocks(cfg, rest, dtype)
    d = cfg.d_model
    params: dict = {"layers": stack, "final_norm": ln()}
    if cfg.layout == "hybrid":
        params["shared"] = {
            "ln1": ln(), "ln2": ln(),
            "attn": one_attn(), "mlp": one_mlp(cfg.d_ff),
        }
    vp = cfg.vocab if cfg.tie_embeddings else sh.pad_vocab(cfg.vocab)
    params["embed"] = (jax.random.normal(next(rest), (vp, d))
                       * d ** -0.5).astype(dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(next(rest), (d, vp))
                             * d ** -0.5).astype(dtype)
    return params


# =============================================================================
# embeddings / unembedding
# =============================================================================

def embed_in(params: dict, cfg: ArchConfig, batch: dict,
             mi: sh.MeshInfo | None) -> jnp.ndarray:
    if cfg.input_mode == "embeds":
        h = batch["embeds"]
    else:
        tokens = batch["tokens"]
        if cfg.tie_embeddings:
            # vocab-sharded table: one-hot matmul (GShard-style lookup)
            oh = jax.nn.one_hot(tokens, params["embed"].shape[0],
                                dtype=params["embed"].dtype)
            h = jnp.einsum("bsv,vd->bsd", oh, params["embed"])
        else:
            h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    return h


def logits_out(params: dict, cfg: ArchConfig, h: jnp.ndarray) -> jnp.ndarray:
    """float32 logits whatever the weights' dtype (bf16 logits would round
    away the margins greedy sampling decides on)."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"],
                            preferred_element_type=jnp.float32)
    vp = logits.shape[-1]
    if vp != cfg.vocab:  # mask padded vocab columns
        pad_mask = jnp.arange(vp) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e9, logits)
    return logits


def _rope_tables(cfg: ArchConfig, positions: jnp.ndarray):
    """(cos, sin) tables; gemma3 gets a second global-theta pair."""
    if cfg.mrope_sections is not None:
        # text-only degenerate M-RoPE: all three streams = token index
        pos3 = jnp.stack([positions] * len(cfg.mrope_sections), axis=-1)
        c, s = layers.mrope_angles(pos3, cfg.head_dim, cfg.rope_theta,
                                   cfg.mrope_sections)
        return (c, s), (c, s)
    c, s = layers.rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    if cfg.rope_theta_global is not None:
        cg, sg = layers.rope_angles(positions, cfg.head_dim,
                                    cfg.rope_theta_global)
        return (c, s), (cg, sg)
    return (c, s), (c, s)


# =============================================================================
# layer bodies
# =============================================================================

def _ffn(lp: dict, cfg: ArchConfig, x: jnp.ndarray, mi: sh.MeshInfo | None,
         valid: jnp.ndarray | None = None):
    """MLP or MoE; returns (y, expert_counts|None, aux_loss).

    ``valid`` (optional bool [B, S]) masks padding rows out of the MoE
    expert-count histogram — bucketed prefill pads sequences, and pad
    rows must not inflate the expert-hotness signal.  The routing/output
    math is untouched (pad rows still flow through and are discarded by
    the caller), only ``counts`` is recomputed from real rows.
    """
    if cfg.is_moe:
        p = moe_mod.MoEParams(**lp["moe"])
        y, (probs, idx, counts) = moe_mod.moe_apply(
            x, p, top_k=cfg.top_k,
            mesh=mi.mesh if mi is not None else None,
            dp_axes=mi.dp_axes if mi is not None else ("data",),
            model_axis=mi.model_axis if mi is not None else "model",
            capacity_factor=cfg.moe_capacity_factor,
            softmax_before_topk=cfg.softmax_before_topk)
        aux = moe_mod.aux_load_balance_loss(
            probs.reshape(-1, cfg.n_experts), idx.reshape(-1, cfg.top_k),
            cfg.n_experts)
        if valid is not None:
            idx_f = idx.reshape(-1, cfg.top_k)
            vrow = valid.reshape(-1).astype(jnp.int32)
            counts = jnp.zeros((cfg.n_experts,), jnp.int32).at[
                idx_f.reshape(-1)].add(
                    jnp.broadcast_to(vrow[:, None], idx_f.shape).reshape(-1))
        return y, counts, aux
    if cfg.mlp_kind == "gelu":
        return layers.gelu_mlp(x, lp["mlp"]["w_up"], lp["mlp"]["w_down"]), None, 0.0
    m = lp["mlp"]
    return layers.swiglu_mlp(x, m["w_gate"], m["w_up"], m["w_down"]), None, 0.0


def _attn_block(lp: dict, cfg: ArchConfig, h, positions, ropes, window,
                use_global, mi: sh.MeshInfo | None, unrolled: bool = False):
    """Pre-norm attention block with optional gemma post-norm."""
    (cl, sl), (cg, sg) = ropes
    cos = jnp.where(use_global, cg, cl) if cfg.rope_theta_global else cl
    sin = jnp.where(use_global, sg, sl) if cfg.rope_theta_global else sl
    x = layers.rms_norm(h, lp["ln1"], eps=cfg.norm_eps,
                        gemma_style=cfg.gemma_norm)
    p = _attn_from_dict(lp["attn"])
    out, _ = attn_mod.attention(p, x, positions, cos, sin, window=window,
                                soft_cap=cfg.soft_cap,
                                q_chunk=cfg.attn_q_chunk, unrolled=unrolled)
    if cfg.gemma_norm:
        out = layers.rms_norm(out, lp["ln1_post"], eps=cfg.norm_eps,
                              gemma_style=True)
    return h + out


def _ffn_block(lp: dict, cfg: ArchConfig, h, mi: sh.MeshInfo | None,
               valid: jnp.ndarray | None = None):
    x = layers.rms_norm(h, lp["ln2"], eps=cfg.norm_eps,
                        gemma_style=cfg.gemma_norm)
    y, counts, aux = _ffn(lp, cfg, x, mi, valid=valid)
    if cfg.gemma_norm:
        y = layers.rms_norm(y, lp["ln2_post"], eps=cfg.norm_eps,
                            gemma_style=True)
    return h + y, counts, aux


def _shared_attn_block(sp: dict, cfg: ArchConfig, h, positions, ropes,
                       mi: sh.MeshInfo | None, unrolled: bool = False):
    """zamba2 shared transformer block (weights reused at every site)."""
    (cl, sl), _ = ropes
    x = layers.rms_norm(h, sp["ln1"], eps=cfg.norm_eps)
    p = _attn_from_dict(sp["attn"])
    out, _ = attn_mod.attention(p, x, positions, cl, sl,
                                q_chunk=cfg.attn_q_chunk, unrolled=unrolled)
    h = h + out
    x = layers.rms_norm(h, sp["ln2"], eps=cfg.norm_eps)
    m = sp["mlp"]
    h = h + layers.swiglu_mlp(x, m["w_gate"], m["w_up"], m["w_down"])
    return h


# =============================================================================
# training / prefill forward (scan over layers)
# =============================================================================

def _layer_arrays(cfg: ArchConfig):
    """Static per-layer scan inputs: window, is_global, apply_shared."""
    L = cfg.n_layers
    wins = cfg.attn_window_pattern or [0] * L
    window = jnp.asarray(wins, jnp.int32)
    use_global = jnp.asarray([w == 0 for w in wins], bool)
    if cfg.layout == "hybrid" and cfg.shared_attn_every:
        k = cfg.shared_attn_every
        shared = jnp.asarray([(i % k) == (k - 1) for i in range(L)], bool)
    else:
        shared = jnp.zeros((L,), bool)
    return window, use_global, shared


def forward_hidden(params: dict, cfg: ArchConfig, batch: dict,
                   mi: sh.MeshInfo | None = None,
                   unrolled: bool = False) -> tuple[jnp.ndarray, dict]:
    """Hidden states [B, S, d] + metrics (expert counts, aux loss).

    unrolled=True python-loops the layers with static per-layer decisions
    (no lax.scan / lax.cond) — used by the dry-run analysis lowering so
    cost_analysis counts every layer exactly once (XLA counts while-loop
    bodies a single time regardless of trip count).
    """
    h = embed_in(params, cfg, batch, mi)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ropes = _rope_tables(cfg, positions)
    window_a, use_global_a, shared_a = _layer_arrays(cfg)
    mspec = mamba_spec_of(cfg) if cfg.layout in ("mamba", "hybrid") else None
    aspec = sh.act_spec(cfg, mi, seq=True) if mi else None

    if unrolled:
        wins = cfg.attn_window_pattern or [0] * cfg.n_layers
        aux = jnp.float32(0.0)
        counts = (jnp.zeros((cfg.n_experts,), jnp.int32) if cfg.is_moe
                  else None)
        k_every = cfg.shared_attn_every

        def one_layer(h, l):
            lp = _layer_params(params, l)
            if cfg.layout in ("mamba", "hybrid"):
                x = layers.rms_norm(h, lp["ln"], eps=cfg.norm_eps)
                mp = ssm.MambaParams(**lp["mamba"])
                h = h + ssm.mamba_forward(mp, mspec, x)
                if cfg.layout == "hybrid" and k_every and \
                        (l % k_every) == (k_every - 1):
                    h = _shared_attn_block(params["shared"], cfg, h,
                                           positions, ropes, mi,
                                           unrolled=True)
                return h, None, 0.0
            w = wins[l]
            h = _attn_block(lp, cfg, h, positions, ropes,
                            jnp.int32(w), jnp.asarray(w == 0), mi,
                            unrolled=True)
            return _ffn_block(lp, cfg, h, mi)

        for l in range(cfg.n_layers):
            fn = jax.checkpoint(one_layer, static_argnums=(1,)) \
                if cfg.remat else one_layer
            h, c, a = fn(h, l)
            aux = aux + a
            if counts is not None and c is not None:
                counts = counts + c
            if mi is not None:
                h = sh.constrain(h, mi, aspec)
        h = layers.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                            gemma_style=cfg.gemma_norm)
        metrics = {"moe_aux": aux}
        if counts is not None:
            metrics["expert_counts"] = counts
        return h, metrics

    def body(carry, xs):
        h, aux_acc, counts_acc = carry
        lp, window, use_global, shared = xs
        if cfg.layout in ("mamba", "hybrid"):
            x = layers.rms_norm(h, lp["ln"], eps=cfg.norm_eps)
            mp = ssm.MambaParams(**lp["mamba"])
            h = h + ssm.mamba_forward(mp, mspec, x)
            if cfg.layout == "hybrid":
                h = jax.lax.cond(
                    shared,
                    lambda hh: _shared_attn_block(params["shared"], cfg, hh,
                                                  positions, ropes, mi),
                    lambda hh: hh, h)
            counts = None
            aux = 0.0
        else:
            h = _attn_block(lp, cfg, h, positions, ropes, window,
                            use_global, mi)
            h, counts, aux = _ffn_block(lp, cfg, h, mi)
        if mi is not None:
            h = sh.constrain(h, mi, aspec)
        aux_acc = aux_acc + aux
        if counts_acc is not None and counts is not None:
            counts_acc = counts_acc + counts
        return (h, aux_acc, counts_acc), None

    counts0 = (jnp.zeros((cfg.n_experts,), jnp.int32) if cfg.is_moe else None)
    body_fn = jax.checkpoint(body) if cfg.remat else body
    (h, aux, counts), _ = jax.lax.scan(
        body_fn, (h, jnp.float32(0.0), counts0),
        (params["layers"], window_a, use_global_a, shared_a))
    h = layers.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                        gemma_style=cfg.gemma_norm)
    metrics = {"moe_aux": aux}
    if counts is not None:
        metrics["expert_counts"] = counts
    return h, metrics


def loss_fn(params: dict, cfg: ArchConfig, batch: dict,
            mi: sh.MeshInfo | None = None, unrolled: bool = False):
    h, metrics = forward_hidden(params, cfg, batch, mi, unrolled=unrolled)
    logits = logits_out(params, cfg, h)
    loss = layers.softmax_cross_entropy(logits, batch["labels"])
    total = loss + cfg.aux_loss_weight * metrics["moe_aux"]
    metrics = dict(metrics, ce_loss=loss)
    return total, metrics


# =============================================================================
# decode (python loop over layers; heterogeneous caches)
# =============================================================================

def init_decode_state(cfg: ArchConfig, batch_size: int, cache_len: int,
                      dtype=jnp.float32, start_pos: int = 0) -> dict:
    """Empty caches sized for ``cache_len`` total context.

    Windowed layers get ring buffers of their window size; full-attention
    layers get ``cache_len`` slots; SSM layers get O(1) state.
    """
    B = batch_size
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    state: dict = {
        "positions": jnp.full((B,), start_pos, jnp.int32),
        "attn": [], "mamba": [],
    }
    kv_dtype = jnp.int8 if cfg.kv_cache_quant else dtype
    wins = cfg.attn_window_pattern
    for w in wins:
        W = min(w, cache_len) if w > 0 else cache_len
        c = {
            "k": jnp.zeros((B, W, Hkv, Dh), kv_dtype),
            "v": jnp.zeros((B, W, Hkv, Dh), kv_dtype),
            "pos": jnp.full((B, W), -1, jnp.int32),
        }
        if cfg.kv_cache_quant:
            c["k_scale"] = jnp.zeros((B, W, Hkv), jnp.float32)
            c["v_scale"] = jnp.zeros((B, W, Hkv), jnp.float32)
        state["attn"].append(c)
    if cfg.layout in ("mamba", "hybrid"):
        spec = mamba_spec_of(cfg)
        for _ in range(cfg.n_layers):
            state["mamba"].append({
                "h": jnp.zeros((B, spec.n_heads, spec.d_state, spec.headdim),
                               jnp.float32),
                "conv": jnp.zeros((B, spec.d_conv - 1, spec.conv_ch), dtype),
            })
        if cfg.layout == "hybrid":
            k = cfg.shared_attn_every
            n_sites = sum(1 for i in range(cfg.n_layers) if (i % k) == (k - 1))
            state["attn"] = [{
                "k": jnp.zeros((B, cache_len, Hkv, Dh), dtype),
                "v": jnp.zeros((B, cache_len, Hkv, Dh), dtype),
                "pos": jnp.full((B, cache_len), -1, jnp.int32),
            } for _ in range(n_sites)]
    return state


def _tree_slice(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def _layer_params(params: dict, i: int):
    """Layer i's params — stacked arrays (lax.scan layout) or an unstacked
    per-layer list (serve layout: avoids re-reading the whole stacked
    tensor per layer in python-loop decode/prefill)."""
    lay = params["layers"]
    return lay[i] if isinstance(lay, list) else _tree_slice(lay, i)


def unstack_params(params: dict, n_layers: int) -> dict:
    """Convert stacked layer params to the per-layer serve layout."""
    return {**params,
            "layers": [_tree_slice(params["layers"], i)
                       for i in range(n_layers)]}


def decode_step(params: dict, cfg: ArchConfig, state: dict, batch: dict,
                mi: sh.MeshInfo | None = None):
    """One-token decode.  batch: {"tokens": [B,1]} or {"embeds": [B,1,d]}.
    Returns (logits [B,1,vocab_padded], new state)."""
    h = embed_in(params, cfg, batch, mi)
    B = h.shape[0]
    pos = state["positions"]                     # [B]
    positions = pos[:, None]
    ropes = _rope_tables(cfg, positions)
    (cl, sl), (cg, sg) = ropes
    wins = cfg.attn_window_pattern
    mspec = mamba_spec_of(cfg) if cfg.layout in ("mamba", "hybrid") else None
    new_attn = list(state["attn"])
    new_mamba = list(state["mamba"])
    kvspec = sh.kv_cache_spec(mi) if mi else None

    ai = 0
    for l in range(cfg.n_layers):
        lp = _layer_params(params, l)
        if cfg.layout in ("mamba", "hybrid"):
            x = layers.rms_norm(h, lp["ln"], eps=cfg.norm_eps)
            mp = ssm.MambaParams(**lp["mamba"])
            out, hs, cs = ssm.mamba_decode_step(
                mp, mspec, x, state["mamba"][l]["h"], state["mamba"][l]["conv"])
            h = h + out
            new_mamba[l] = {"h": hs, "conv": cs}
            k_every = cfg.shared_attn_every
            if cfg.layout == "hybrid" and k_every and (l % k_every) == (k_every - 1):
                sp = params["shared"]
                x = layers.rms_norm(h, sp["ln1"], eps=cfg.norm_eps)
                c = state["attn"][ai]
                p = _attn_from_dict(sp["attn"])
                out, kc, vc, pc = attn_mod.decode_attention(
                    p, x, c["k"], c["v"], c["pos"], positions, cl, sl)
                h = h + out
                x = layers.rms_norm(h, sp["ln2"], eps=cfg.norm_eps)
                m = sp["mlp"]
                h = h + layers.swiglu_mlp(x, m["w_gate"], m["w_up"], m["w_down"])
                new_attn[ai] = {"k": kc, "v": vc, "pos": pc}
                ai += 1
        else:
            w = wins[l]
            is_global = (w == 0)
            cos = cg if (is_global and cfg.rope_theta_global) else cl
            sin = sg if (is_global and cfg.rope_theta_global) else sl
            x = layers.rms_norm(h, lp["ln1"], eps=cfg.norm_eps,
                                gemma_style=cfg.gemma_norm)
            c = state["attn"][l]
            p = _attn_from_dict(lp["attn"])
            res = attn_mod.decode_attention(
                p, x, c["k"], c["v"], c["pos"], positions, cos, sin,
                window=(w if w > 0 else None), soft_cap=cfg.soft_cap,
                k_scale=c.get("k_scale"), v_scale=c.get("v_scale"))
            if cfg.kv_cache_quant:
                out, kc, vc, pc, ks, vs = res
            else:
                out, kc, vc, pc = res
            if cfg.gemma_norm:
                out = layers.rms_norm(out, lp["ln1_post"], eps=cfg.norm_eps,
                                      gemma_style=True)
            h = h + out
            h, _, _ = _ffn_block(lp, cfg, h, mi)
            if mi is not None:
                kc = sh.constrain(kc, mi, kvspec)
                vc = sh.constrain(vc, mi, kvspec)
            nc = {"k": kc, "v": vc, "pos": pc}
            if cfg.kv_cache_quant:
                nc["k_scale"] = ks
                nc["v_scale"] = vs
            new_attn[l] = nc

    h = layers.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                        gemma_style=cfg.gemma_norm)
    logits = logits_out(params, cfg, h)
    new_state = {"positions": pos + 1, "attn": new_attn, "mamba": new_mamba}
    return logits, new_state


def prefill(params: dict, cfg: ArchConfig, batch: dict, cache_len: int,
            mi: sh.MeshInfo | None = None, unrolled: bool = False):
    """Process a full prompt; returns (last-token logits, decode state).

    Python loop over layers so each layer's K/V lands directly in its cache
    (ring-placed for windowed layers)."""
    h = embed_in(params, cfg, batch, mi)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ropes = _rope_tables(cfg, positions)
    (cl, sl), (cg, sg) = ropes
    wins = cfg.attn_window_pattern
    mspec = mamba_spec_of(cfg) if cfg.layout in ("mamba", "hybrid") else None
    state = init_decode_state(cfg, B, cache_len, dtype=h.dtype, start_pos=S)
    kvspec = sh.kv_cache_spec(mi) if mi else None

    def place(cache, k, v):
        W = cache["k"].shape[1]
        n = min(S, W)
        idx = (jnp.arange(S - n, S) % W).astype(jnp.int32)
        out = {}
        if cfg.kv_cache_quant:
            def q8(u):
                sc = jnp.maximum(jnp.max(jnp.abs(u.astype(jnp.float32)), -1)
                                 / 127.0, 1e-8)
                return (jnp.clip(jnp.round(u / sc[..., None]), -127, 127)
                        .astype(jnp.int8), sc)
            k8, ks = q8(k[:, S - n:])
            v8, vs = q8(v[:, S - n:])
            kc = cache["k"].at[:, idx].set(k8)
            vc = cache["v"].at[:, idx].set(v8)
            out["k_scale"] = cache["k_scale"].at[:, idx].set(ks)
            out["v_scale"] = cache["v_scale"].at[:, idx].set(vs)
        else:
            kc = cache["k"].at[:, idx].set(k[:, S - n:].astype(cache["k"].dtype))
            vc = cache["v"].at[:, idx].set(v[:, S - n:].astype(cache["v"].dtype))
        pc = cache["pos"].at[:, idx].set(jnp.arange(S - n, S, dtype=jnp.int32))
        if mi is not None:
            kc = sh.constrain(kc, mi, kvspec)
            vc = sh.constrain(vc, mi, kvspec)
        return {"k": kc, "v": vc, "pos": pc, **out}

    ai = 0
    for l in range(cfg.n_layers):
        lp = _layer_params(params, l)
        if cfg.layout in ("mamba", "hybrid"):
            x = layers.rms_norm(h, lp["ln"], eps=cfg.norm_eps)
            mp = ssm.MambaParams(**lp["mamba"])
            out, (hs, conv_tail) = ssm.mamba_forward(mp, mspec, x,
                                                     return_state=True)
            h = h + out
            state["mamba"][l] = {"h": hs, "conv": conv_tail.astype(h.dtype)}
            k_every = cfg.shared_attn_every
            if cfg.layout == "hybrid" and k_every and (l % k_every) == (k_every - 1):
                sp = params["shared"]
                x = layers.rms_norm(h, sp["ln1"], eps=cfg.norm_eps)
                p = _attn_from_dict(sp["attn"])
                out, (k, v) = attn_mod.attention(p, x, positions, cl, sl)
                h = h + out
                x = layers.rms_norm(h, sp["ln2"], eps=cfg.norm_eps)
                m = sp["mlp"]
                h = h + layers.swiglu_mlp(x, m["w_gate"], m["w_up"], m["w_down"])
                state["attn"][ai] = place(state["attn"][ai], k, v)
                ai += 1
        else:
            w = wins[l]
            is_global = (w == 0)
            cos = cg if (is_global and cfg.rope_theta_global) else cl
            sin = sg if (is_global and cfg.rope_theta_global) else sl
            x = layers.rms_norm(h, lp["ln1"], eps=cfg.norm_eps,
                                gemma_style=cfg.gemma_norm)
            p = _attn_from_dict(lp["attn"])
            out, (k, v) = attn_mod.attention(
                p, x, positions, cos, sin, window=(w if w > 0 else None),
                soft_cap=cfg.soft_cap, q_chunk=cfg.attn_q_chunk,
                unrolled=unrolled)
            if cfg.gemma_norm:
                out = layers.rms_norm(out, lp["ln1_post"], eps=cfg.norm_eps,
                                      gemma_style=True)
            h = h + out
            h, _, _ = _ffn_block(lp, cfg, h, mi)
            state["attn"][l] = place(state["attn"][l], k, v)
        if mi is not None:
            h = sh.constrain(h, mi, sh.act_spec(cfg, mi, seq=True))

    h = layers.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                        gemma_style=cfg.gemma_norm)
    logits = logits_out(params, cfg, h[:, -1:, :])
    return logits, state
