"""Plain float32 reference of the Qwen1.5 decoder, and the weights the
benchmark draws for it.

The architecture as published for Qwen1.5 (the Qwen2 code of Hugging
Face transformers): token embedding; per layer a pre-norm (RMSNorm)
grouped-query attention with biased q/k/v projections, rotary position
embedding (rotate-half form, ``theta`` from the sizes) and an unbiased
output projection, then a pre-norm SwiGLU MLP (``down(silu(gate(x)) *
up(x))``); a final RMSNorm; logits against the tied embedding.  Causal
attention scaled by ``1/sqrt(head_dim)``.  Departures: none in the
mathematics; the weights are random (drawn from a seed), not trained.

Everything runs in float32 under ``jax.default_matmul_precision
("highest")``, layer by layer over one sequence, and the logits in
blocks of positions, so that it fits beside nothing else on one chip.
It imports nothing of the program.

:func:`draw_weights` makes the weights from a seed on the device, in
one jitted call, in the layout of the program's parameter tree (layers
stacked on a leading axis) and in the dtype they are served in; the
reference draws the same values again from the same seed.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.02      # spread of the q/k/v biases
NORM_STD = 0.1       # spread of the RMSNorm gains around 1


def _shapes(sz: Dict[str, Any]) -> Dict[str, Any]:
    d, h, kv = sz["hidden_size"], sz["num_attention_heads"], \
        sz["num_key_value_heads"]
    hd, f, v, n = d // h, sz["intermediate_size"], sz["vocab_size"], \
        sz["num_hidden_layers"]
    return {
        "embed": (v, d),
        "layers": {
            "attn_norm": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                     "wv": (n, d, kv, hd), "wo": (n, h, hd, d),
                     "bq": (n, h, hd), "bk": (n, kv, hd),
                     "bv": (n, kv, hd)},
            "ffn_norm": {"scale": (n, d)},
            "mlp": {"w_gate": (n, d, f), "w_up": (n, d, f),
                    "w_down": (n, f, d)},
        },
        "final_norm": {"scale": (d,)},
    }


def _fan_in(path: str, shape, sz) -> int:
    if "wo" in path:
        return shape[-3] * shape[-2]
    if "w_down" in path:
        return shape[-2]
    return sz["hidden_size"]


def draw_weights(sz: Dict[str, Any], seed: int, dtype=jnp.bfloat16):
    """The whole parameter tree from ``seed``, on the device, in one
    jitted call: every matrix normal with variance 1/fan_in (fan_in =
    its input width: the model width, or the heads' or the MLP's width
    for the output projections; the embedding is read as a matrix of
    input width ``hidden_size``), so that every layer's output and
    every attention score is of order one and the served tokens depend
    on the context; biases normal(0, BIAS_STD); RMSNorm gains
    1 + normal(0, NORM_STD)."""
    shapes = _shapes(sz)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape, path in zip(keys, leaves, paths):
            x = jax.random.normal(k, shape, jnp.float32)
            if "norm" in path:
                x = 1.0 + NORM_STD * x
            elif "'b" in path:
                x = BIAS_STD * x
            else:
                x = x / np.sqrt(_fan_in(path, shape, sz))
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    key = jax.random.key(np.uint32(seed % (1 << 32)))
    return jax.jit(make)(key)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]     # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, sz):
    """One decoder layer over a whole sequence x (T, d), float32."""
    t = x.shape[0]
    h, kv = sz["num_attention_heads"], sz["num_key_value_heads"]
    eps, theta = sz["rms_norm_eps"], sz["rope_theta"]
    pos = jnp.arange(t)
    a = _rms(x, p["attn_norm"]["scale"], eps)
    q = jnp.einsum("td,dhk->thk", a, p["attn"]["wq"]) + p["attn"]["bq"]
    k = jnp.einsum("td,dhk->thk", a, p["attn"]["wk"]) + p["attn"]["bk"]
    v = jnp.einsum("td,dhk->thk", a, p["attn"]["wv"]) + p["attn"]["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = h // kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, -1), v)
    x = x + jnp.einsum("thk,hkd->td", o, p["attn"]["wo"])
    m = _rms(x, p["ffn_norm"]["scale"], eps)
    g = jnp.einsum("td,df->tf", m, p["mlp"]["w_gate"])
    u = jnp.einsum("td,df->tf", m, p["mlp"]["w_up"])
    return x + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u,
                          p["mlp"]["w_down"])


class Reference:
    """The float32 forward over given weights (a tree as
    :func:`draw_weights` lays it out), one sequence at a time."""

    def __init__(self, sz: Dict[str, Any], weights, block: int = 128):
        self.sz = sz
        self.w = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                              weights)
        self.block = block
        self._layer = jax.jit(lambda x, p: _layer(x, p, sz))
        self._head = jax.jit(self._head_fn)

    def _head_fn(self, x, g, emb):
        x = _rms(x, g, self.sz["rms_norm_eps"])
        return jnp.einsum("td,vd->tv", x, emb)

    def logits(self, tokens: np.ndarray) -> np.ndarray:
        """(T, V) float32 logits of every position of ``tokens``."""
        with jax.default_matmul_precision("highest"):
            x = self.w["embed"][jnp.asarray(tokens)]
            n = self.sz["num_hidden_layers"]
            for i in range(n):
                x = self._layer(x, jax.tree.map(lambda a: a[i],
                                                self.w["layers"]))
            out = []
            for lo in range(0, x.shape[0], self.block):
                out.append(np.asarray(self._head(
                    x[lo: lo + self.block], self.w["final_norm"]["scale"],
                    self.w["embed"])))
        return np.concatenate(out)


def served_gap(logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Per served token: how far its logit lies below the best logit at
    the position that produced it (0 where it is the argmax)."""
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]
