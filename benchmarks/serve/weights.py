"""Random weights from a seed, made by the benchmark and not by the program.

The served pytree is the program's dense-family layout (stacked layers under
``layers``); the reference remakes any one layer from the same seed, bit for
bit, with :func:`layer_params`, so it never reads what the program holds.
Keys travel as arguments, never as constants, so every seed runs the same
compiled programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_SPREAD = 0.1          # norm gains are 1 + 0.1 * N(0, 1), not all ones


def root_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _split(key):
    """(embed, head, final norm, layers) keys."""
    return jax.random.split(key, 4)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _gain(key, d, dtype):
    return (1.0 + NORM_SPREAD * jax.random.normal(key, (d,), jnp.float32)
            ).astype(dtype)


def layer_params(key, a, dtype=jnp.bfloat16) -> dict:
    """One decoder layer; ``a`` is the program's ArchConfig."""
    d, h, hk, dh, f = a.d_model, a.n_heads, a.n_kv_heads, a.head_dim(), a.d_ff
    ks = jax.random.split(key, 9)
    return {
        "attn": {"wq": _normal(ks[0], (d, h * dh), d ** -0.5, dtype),
                 "wk": _normal(ks[1], (d, hk * dh), d ** -0.5, dtype),
                 "wv": _normal(ks[2], (d, hk * dh), d ** -0.5, dtype),
                 "wo": _normal(ks[3], (h * dh, d), (h * dh) ** -0.5, dtype),
                 "norm": _gain(ks[4], d, dtype)},
        "mlp": {"w_gate": _normal(ks[5], (d, f), d ** -0.5, dtype),
                "w_up": _normal(ks[6], (d, f), d ** -0.5, dtype),
                "w_down": _normal(ks[7], (f, d), f ** -0.5, dtype)},
        "mlp_norm": _gain(ks[8], d, dtype),
    }


def layer_keys(key, a):
    return jax.random.split(_split(key)[3], a.n_layers)


def embed(key, a, dtype=jnp.bfloat16):
    return _normal(_split(key)[0], (a.vocab, a.d_model), 1.0, dtype)


def head(key, a, dtype=jnp.bfloat16):
    return _normal(_split(key)[1], (a.d_model, a.vocab), a.d_model ** -0.5,
                   dtype)


def final_norm(key, a, dtype=jnp.bfloat16):
    return _gain(_split(key)[2], a.d_model, dtype)


def _build(key, a, dtype):
    return {
        "embed": embed(key, a, dtype),
        "final_norm": final_norm(key, a, dtype),
        "lm_head": head(key, a, dtype),
        "layers": jax.vmap(lambda k: layer_params(k, a, dtype))(
            layer_keys(key, a)),
    }


def make_params(seed: int, a, dtype=jnp.bfloat16) -> dict:
    """The whole served pytree, on the device, in one jitted call."""
    return jax.jit(functools.partial(_build, a=a, dtype=dtype))(
        root_key(seed))
