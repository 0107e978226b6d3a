"""Plain float32 reference of the toy routed decoder (``model.py``
beside it): the dense reference's attention, norms and head, and a routed
SwiGLU expert layer computed for every expert and weighted by the router
(softmax over the experts, the ``top_k`` largest kept and renormalised),
as ``repro.models.moe`` routes with its defaults."""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp

from benchlib.spec import load_module

_dense = load_module(Path(__file__).with_name("dense.py"))
HI = _dense.HI
gaps = _dense.gaps


class Reference(_dense.Reference):
    def _ffn(self, lp, x):
        m = lp["moe"]
        probs = jax.nn.softmax(jnp.einsum(
            "td,de->te", x, self._mat(m["w_router"], 0), precision=HI),
            axis=-1)
        w, idx = jax.lax.top_k(probs, self.c["top_k"])
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        rows = jnp.arange(x.shape[0])[:, None]
        weight = jnp.zeros_like(probs).at[rows, idx].set(w)
        gate = jnp.einsum("td,edf->tef", x, self._mat(m["w_gate"], 1),
                          precision=HI)
        up = jnp.einsum("td,edf->tef", x, self._mat(m["w_up"], 1),
                        precision=HI)
        y = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * up,
                       self._mat(m["w_down"], 1), precision=HI)
        return jnp.einsum("te,ted->td", weight, y, precision=HI)
