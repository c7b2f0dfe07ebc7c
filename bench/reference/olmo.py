"""Plain float32 reference of the OLMo decoder (arXiv:2402.00838), and the
benchmark's own random weights for it.

The published architecture: token embedding; per layer a non-parametric
LayerNorm (no scale, no bias, eps 1e-5), causal multi-head attention
with rotary embeddings (theta 10000, the two halves of each head
rotated), a residual add, another non-parametric LayerNorm and a SwiGLU
MLP (``silu(x Wg) * (x Wu)) Wd``), a residual add; a final
non-parametric LayerNorm and logits from the tied embedding. No biases.

Everything runs in float32 with ``precision=HIGHEST`` (on a TPU a
float32 product otherwise rounds its operands to bfloat16), one whole
sequence at a time, layer by layer under a ``lax.scan``, so a sequence
of the model's full context fits beside nothing else. Nothing here
imports the program under test.

``lowp=True`` is the control: the same forward with the operands of
every matrix product rounded to float8 (e4m3, scaled per row so that the
largest magnitude fits), the precision below the bfloat16 the model is
served in.

``program_params`` lays the same arrays out as the program's parameter
tree, for the serving kind to hand to the engine under test: the
weights are the benchmark's, made here, and the program makes none of
them. A configuration names this module under ``reference``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
ROPE_THETA = 10000.0


def make_weights(m: Dict[str, Any], key, vocab_rows: int,
                 dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Random weights in the served dtype, made on the device in one
    jitted call. Scales: embedding 0.02; q, k, v, gate, up fan-in^-1/2;
    the two projections into the residual stream fan-in^-1/2 / sqrt(2 L).
    ``vocab_rows`` may pad the embedding past the vocabulary (the padded
    rows are never read)."""
    L, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    h, kv = m["n_heads"], m["n_kv_heads"]
    dh = d // h
    deep = (2 * L) ** -0.5
    shapes = {
        "embed": ((vocab_rows, d), 0.02),
        "wq": ((L, d, h, dh), d ** -0.5),
        "wk": ((L, d, kv, dh), d ** -0.5),
        "wv": ((L, d, kv, dh), d ** -0.5),
        "wo": ((L, h * dh, d), (h * dh) ** -0.5 * deep),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5 * deep),
    }

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return {name: (scale * jax.random.normal(k, shape, jnp.float32)
                       ).astype(dtype)
                for k, (name, (shape, scale)) in zip(keys, shapes.items())}

    return make(key)


def program_params(w: Dict[str, Any]) -> Dict[str, Any]:
    """The weights in the program's parameter tree for an olmo
    configuration: one scanned segment of dense blocks, non-parametric
    norms, tied head."""
    return {"embed": {"table": w["embed"]}, "final_norm": {},
            "blocks": {"ln1": {}, "ln2": {},
                       "attn": {"q": {"w": w["wq"]}, "k": {"w": w["wk"]},
                                "v": {"w": w["wv"]}, "o": {"w": w["wo"]}},
                       "ffn": {"gate": {"w": w["w_gate"]},
                               "up": {"w": w["w_up"]},
                               "down": {"w": w["w_down"]}}}}


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x: jax.Array, w: jax.Array, lowp: bool) -> jax.Array:
    """``x [..., k] @ w [k, ...]`` in float32 (control: float8 operands,
    x scaled per row, w per output column)."""
    w = w.reshape(w.shape[0], -1).astype(jnp.float32)
    if lowp:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _ln(x: jax.Array) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _rope(x: jax.Array, pos: jax.Array) -> jax.Array:
    """x [S, H, dh]: rotate the pair (i, i + dh/2) by pos * theta^(-2i/dh)."""
    half = x.shape[-1] // 2
    inv = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("vocab", "lowp"))
def logits_at(w: Dict[str, jax.Array], tokens: jax.Array, at: jax.Array,
              *, vocab: int, lowp: bool = False) -> jax.Array:
    """Logits ``[len(at), vocab]`` of the sequence ``tokens [S]`` at the
    positions ``at``. Positions past a sequence's end may pad ``tokens``:
    attention is causal, so they change nothing before them."""
    s = tokens.shape[0]
    h = w["wq"].shape[2]
    kvh = w["wk"].shape[2]
    dh = w["wq"].shape[3]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        a = _ln(x)
        q = _rope(_mm(a, lw["wq"], lowp).reshape(s, h, dh), pos)
        k = _rope(_mm(a, lw["wk"], lowp).reshape(s, kvh, dh), pos)
        v = _mm(a, lw["wv"], lowp).reshape(s, kvh, dh)
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
        if lowp:
            q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * dh ** -0.5
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if lowp:
            p = _q8(p, -1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, h * dh)
        x = x + _mm(o, lw["wo"], lowp)
        a = _ln(x)
        g = _mm(a, lw["w_gate"], lowp)
        u = _mm(a, lw["w_up"], lowp)
        x = x + _mm(jax.nn.silu(g) * u, lw["w_down"], lowp)
        return x, None

    layers = {k: v for k, v in w.items() if k != "embed"}
    x, _ = jax.lax.scan(layer, x, layers)
    x = _ln(x[at])
    head = w["embed"][:vocab].T
    return _mm(x, head, lowp)
