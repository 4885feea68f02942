"""Plain float32 reference of the served dense decoder, layer by layer.

It imports nothing of the program and reads nothing the program made: the
weights are remade from the seed by :mod:`weights`, one layer at a time, and
the forward pass is written out here in ``jax.numpy`` at ``HIGHEST`` matmul
precision.  The block is the published pre-norm decoder: RMSNorm, GQA
attention with rotary position (two-halves rotation), SwiGLU MLP, final
RMSNorm and an untied output head.  Departures a configuration states are
listed in its file.

``mode="fp8"`` is the control: the same pass with every weight and
activation rounded to float8 (e4m3, scaled per row or column) in front of
each projection, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_Q = 512              # query rows per attention block
F8_MAX = 448.0             # largest float8_e4m3fn


def _fq(x, axis):
    """float8 e4m3 round trip with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (T, H, dh); rotate the two halves of each head."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attend(q, k, v):
    """Causal GQA over one sequence: q (T, H, dh), k/v (T, Hk, dh)."""
    t, h, dh = q.shape
    hk = k.shape[1]
    qb = q.reshape(t // BLOCK_Q, BLOCK_Q, hk, h // hk, dh)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HIGHEST) * dh ** -0.5
        qpos = i * BLOCK_Q + jnp.arange(BLOCK_Q)
        s = jnp.where(qpos[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(t // BLOCK_Q), qb))
    return out.reshape(t, h * dh)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _layer(x, w, a, mode):
    """One decoder layer over (S, T, d) float32 activations."""
    t = x.shape[1]
    pos = jnp.arange(t)
    h, hk, dh = a.n_heads, a.n_kv_heads, a.head_dim()

    def one(xs):
        at = w["attn"]
        hn = _rms(xs, at["norm"], a.norm_eps)
        q = _rope(_mm(hn, at["wq"], mode).reshape(t, h, dh), pos,
                  a.rope_theta)
        k = _rope(_mm(hn, at["wk"], mode).reshape(t, hk, dh), pos,
                  a.rope_theta)
        v = _mm(hn, at["wv"], mode).reshape(t, hk, dh)
        xs = xs + _mm(_attend(q, k, v), at["wo"], mode)
        m = w["mlp"]
        hn = _rms(xs, w["mlp_norm"], a.norm_eps)
        gate = jax.nn.silu(_mm(hn, m["w_gate"], mode))
        return xs + _mm(gate * _mm(hn, m["w_up"], mode), m["w_down"], mode)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("a",))
def _embed(key, tokens, a):
    return weights.embed(key, a)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _logits(key, x, flat, a, mode):
    """Logits at rows ``flat`` of the flattened (S*T, d) activations; the
    control returns only each row's top token."""
    rows = x.reshape(-1, x.shape[-1])[flat]
    out = _mm(_rms(rows, weights.final_norm(key, a), a.norm_eps),
              weights.head(key, a), mode)
    return jnp.argmax(out, -1) if mode == "fp8" else out


@functools.partial(jax.jit, static_argnames=("a",))
def _layer_weights(lkey, a):
    return weights.layer_params(lkey, a)


def logits_at(a, seed: int, seqs: List[np.ndarray], flat: np.ndarray,
              length: int, mode: str = "f32") -> jnp.ndarray:
    """Logits (len(flat), vocab) at rows ``flat`` of the sequences laid end
    to end, each right-padded to ``length`` (a multiple of BLOCK_Q; causality
    keeps padding out of every earlier position).  ``mode="fp8"`` returns
    the control's top token per row instead.  Every shape is fixed by the
    cell, so each run reuses the compiled programs."""
    key = weights.root_key(seed)
    toks = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    x = _embed(key, jnp.asarray(toks), a)
    for lkey in weights.layer_keys(key, a):
        x = _layer(x, _layer_weights(lkey, a), a, mode)
    return _logits(key, x, jnp.asarray(flat, jnp.int32), a, mode)
