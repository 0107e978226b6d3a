"""Plain float32 reference of a dense decoder (GQA or MHA, optional qk-norm,
RoPE, SwiGLU, RMSNorm, tied or untied head), for one sequence at a time.

Generalised from ``chip_smoke.Reference``.  It reads the benchmark's own
bf16 weights (never anything the program made), upcasts one layer at a
time inside the compiled layer program (a float32 copy of the whole
model would not fit beside the bf16 one) and computes at the highest
matmul precision.  It imports nothing of the program: sizes come from
the configuration file's ``serve_as`` values.

Published-architecture departures, shared with the engine and noted in
the configuration files: RoPE rotates interleaved pairs (2i, 2i+1) where
the HF models rotate halves, a fixed permutation of head dims that random
weights cannot tell apart; phi3's fused qkv/gate_up projections are kept
as separate matrices.

``quant="int8"`` or ``"fp8"`` is the control: every matrix is stored in
that type per output channel (absmax scaling) before the float32
computation, a precision below the served bf16 weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _round_trip(w, in_axes, quant: str):
    """``w`` stored in ``quant`` with one scale per output channel (absmax
    over ``in_axes``) and read back as float32: int8 rounds to 255 levels,
    fp8 (e4m3) to 3 mantissa bits."""
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=in_axes, keepdims=True),
                       1e-12)
    if quant == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if quant == "fp8":
        s = amax / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quant {quant!r}")


class Reference:
    def __init__(self, sizes: dict, params: dict, seq_len: int,
                 quant: str | None = None):
        self.c = sizes
        self.params = params
        self.T = seq_len
        self.quant = quant
        self.head_dim = sizes.get("d_head") or (sizes["d_model"]
                                                // sizes["n_heads"])
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)
        self._embed = jax.jit(self._embed_fn)

    # -- pieces -------------------------------------------------------------
    @staticmethod
    def _rms(x, w, eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                 + eps) * w

    def _rope(self, x, pos):
        half = x.shape[-1] // 2
        freq = 1.0 / (self.c["rope_theta"]
                      ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = pos[:, None].astype(jnp.float32) * freq          # [T, half]
        c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        xe, xo = x[..., 0::2], x[..., 1::2]
        return jnp.stack([xe * c - xo * s, xe * s + xo * c],
                         axis=-1).reshape(x.shape)

    def _mat(self, w, in_axes):
        w = w.astype(jnp.float32)
        return _round_trip(w, in_axes, self.quant) if self.quant else w

    def _layer_fn(self, layers, l, h):
        c = self.c
        eps = c["norm_eps"]
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, l, keepdims=False), layers)
        a = lp["attn"]
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
        T = h.shape[0]
        pos = jnp.arange(T)
        x = self._rms(h, f32(lp["ln1"]), eps)
        q = jnp.einsum("td,dhk->thk", x, self._mat(a["wq"], 0), precision=HI)
        k = jnp.einsum("td,dhk->thk", x, self._mat(a["wk"], 0), precision=HI)
        v = jnp.einsum("td,dhk->thk", x, self._mat(a["wv"], 0), precision=HI)
        if c.get("qk_norm"):
            q = self._rms(q, f32(a["q_norm"]), eps)
            k = self._rms(k, f32(a["k_norm"]), eps)
        q, k = self._rope(q, pos), self._rope(k, pos)
        g = c["n_heads"] // c["n_kv_heads"]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k, precision=HI) \
            * self.head_dim ** -0.5
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        h = h + jnp.einsum("thk,hkd->td", o, self._mat(a["wo"], (0, 1)),
                           precision=HI)
        return h + self._ffn(lp, self._rms(h, f32(lp["ln2"]), eps))

    def _ffn(self, lp, x):
        """The layer's SwiGLU MLP over the normed hidden states [T, d]."""
        m = lp["mlp"]
        gate = jnp.einsum("td,df->tf", x, self._mat(m["w_gate"], 0),
                          precision=HI)
        up = jnp.einsum("td,df->tf", x, self._mat(m["w_up"], 0),
                        precision=HI)
        return jnp.einsum("tf,fd->td", jax.nn.silu(gate) * up,
                          self._mat(m["w_down"], 0), precision=HI)

    def _embed_fn(self, embed, tokens):
        return self._mat(embed, 1)[tokens]

    def _head_fn(self, final_norm, head, h, pick):
        """Per position: the best logit, the logit of ``pick``, and the
        argmax, over the real vocabulary."""
        x = self._rms(h, final_norm.astype(jnp.float32), self.c["norm_eps"])
        if self.c["tie_embeddings"]:
            logits = jnp.einsum("td,vd->tv", x, self._mat(head, 1),
                                precision=HI)
        else:
            logits = jnp.einsum("td,dv->tv", x, self._mat(head, 0),
                                precision=HI)
        logits = logits[:, :self.c["vocab"]]
        picked = jnp.take_along_axis(logits, pick[:, None], axis=1)[:, 0]
        return (jnp.max(logits, axis=-1), picked,
                jnp.argmax(logits, axis=-1).astype(jnp.int32))

    # -- one sequence ---------------------------------------------------------
    def hidden(self, tokens: list[int]):
        """Final hidden states (on device), teacher-forced over ``tokens``
        padded to the fixed length (causal: the padding never reaches
        their positions)."""
        p = self.params
        toks = np.zeros(self.T, np.int32)
        toks[:len(tokens)] = tokens
        h = self._embed(p["embed"], jnp.asarray(toks))
        for l in range(self.c["n_layers"]):
            h = self._layer(p["layers"], l, h)
        return h

    def head(self, h, n: int, pick=None):
        """numpy (best, picked, argmax), each [n]: at position i, over the
        logits predicting token i + 1; ``picked`` is the logit of
        ``pick[i]``."""
        p = self.params
        pk = np.zeros(self.T, np.int32)
        if pick is not None:
            pk[:len(pick)] = pick
        head = p["embed"] if self.c["tie_embeddings"] else p["lm_head"]
        best, picked, arg = self._head(p["final_norm"], head, h,
                                       jnp.asarray(pk))
        return (np.asarray(best)[:n], np.asarray(picked)[:n],
                np.asarray(arg)[:n])


def gaps(ref: Reference, prompt: list[int], served: list[int],
         control: Reference | None = None):
    """For each served token, the gap by which its reference logit lies
    below the reference's best at that position.  With ``control``, also
    the gap of the token the control puts first at each of those
    positions (teacher-forced on the same served tokens).  Returns
    (gaps, control_gaps or None), numpy [len(served)] each."""
    seq = list(prompt) + list(served)
    ctx = seq[:-1]
    lo = len(prompt) - 1
    h = ref.hidden(ctx)
    pick = np.zeros(len(ctx), np.int32)
    pick[lo:] = served
    best, picked, _ = ref.head(h, len(ctx), pick)
    out = (best - picked)[lo:]
    if control is None:
        return out, None
    _, _, arg = control.head(control.hidden(ctx), len(ctx))
    pick[lo:] = arg[lo:]
    best, picked, _ = ref.head(h, len(ctx), pick)
    return out, (best - picked)[lo:]
