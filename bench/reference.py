"""The plain reference: the decoder the configurations state, in float32
``jax.numpy`` at HIGHEST matmul precision, one layer at a time.

It imports nothing of the program. It reads the benchmark's own weights
(``bench/weights.py``) by their names in the tree, casts each layer's
weights up to float32 inside the layer's program, and runs one sequence at
a time, padded at the end to a fixed length so that a single program
serves every sequence (attention is causal, so the padding changes no
earlier position).

The block, as the configurations' ``departures`` state it: RMSNorm,
rotary positions (NeoX half rotation), multi-head causal attention with
q/k/v biases and no output bias, a GELU (tanh form) MLP without biases, a
final RMSNorm and logits tied to the embedding.

``quant="fp8"`` is the control, the step a later change might be tempted
to take: every projection runs as a float8 e4m3 matmul, its weight (per
layer) and its input activations each rounded to float8 with one scale per
tensor, the embedding too; attention scores, softmax, norms and sums stay
float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


def fp8_roundtrip(x):
    """Round ``x`` to float8 e4m3 with one scale for the whole tensor."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x [S, H, D]; positions 0..S-1; NeoX half rotation."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv               # [S, D/2]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


class Reference:
    """Float32 logits of the configured decoder for one sequence at a
    time. ``params`` is the benchmark's weight tree; ``model`` the
    configuration file's ``model`` section."""

    def __init__(self, params, model: dict, quant: str | None = None):
        if quant not in (None, "fp8"):
            raise ValueError(f"unknown reference precision {quant!r}")
        self.p = params
        self.m = model
        self.quant = quant

    def _w(self, x):
        return fp8_roundtrip(x) if self.quant == "fp8" else x.astype(F32)

    def _a(self, x):
        """A projection's input activations, as the control rounds them."""
        return fp8_roundtrip(x) if self.quant == "fp8" else x

    @functools.partial(jax.jit, static_argnums=0)
    def _embed(self, embed, tokens):
        return self._w(embed)[tokens]

    @functools.partial(jax.jit, static_argnums=0)
    def _layer(self, stacked, layer, h):
        m = self.m
        p = jax.tree.map(lambda x: x[layer], stacked)
        S = h.shape[0]
        H = m["num_heads"]
        hd = m["d_model"] // H
        with jax.default_matmul_precision("highest"):
            at = p["mixer"]
            x = self._a(_rms(h, p["norm1"]["scale"], m["norm_eps"]))
            q = x @ self._w(at["wq"]) + at["bq"].astype(F32)
            k = x @ self._w(at["wk"]) + at["bk"].astype(F32)
            v = x @ self._w(at["wv"]) + at["bv"].astype(F32)
            q = _rope(q.reshape(S, H, hd), m["rope_theta"])
            k = _rope(k.reshape(S, H, hd), m["rope_theta"])
            v = v.reshape(S, H, hd)
            s = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(hd)
            causal = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hst,thd->shd", a, v).reshape(S, H * hd)
            h = h + self._a(o) @ self._w(at["wo"])
            ff = p["ffn"]
            x = self._a(_rms(h, p["norm2"]["scale"], m["norm_eps"]))
            u = self._a(_gelu_tanh(x @ self._w(ff["w_in"])))
            h = h + u @ self._w(ff["w_out"])
        return h

    @functools.partial(jax.jit, static_argnums=0)
    def _head(self, final_scale, embed, h):
        with jax.default_matmul_precision("highest"):
            x = self._a(_rms(h, final_scale, self.m["norm_eps"]))
            return x @ self._w(embed).T

    def _trunk(self, tokens: np.ndarray, pad_to: int):
        seq = np.zeros(pad_to, np.int32)
        seq[:len(tokens)] = tokens
        h = self._embed(self.p["embed"], jnp.asarray(seq))
        stacked = self.p["segment0"]["slot0"]
        for layer in range(self.m["num_layers"]):
            h = self._layer(stacked, jnp.int32(layer), h)
        return h

    def logits(self, tokens: np.ndarray, pad_to: int):
        """[pad_to, V] float32 logits of ``tokens`` right-padded with 0."""
        return self._head(self.p["final_norm"]["scale"], self.p["embed"],
                          self._trunk(tokens, pad_to))


@jax.jit
def greedy_gaps(ref_logits, tokens, positions):
    """How far each token's reference logit lies below the reference's
    best at its position: 0 for the reference's own greedy choice."""
    rows = ref_logits[positions]
    return rows.max(-1) - jnp.take_along_axis(rows, tokens[:, None], -1)[:, 0]


@jax.jit
def first_choice(logits, positions):
    """The token a set of logits puts first at each position."""
    return jnp.argmax(logits[positions], -1).astype(jnp.int32)
