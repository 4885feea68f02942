"""Public wrappers: dense-cache and paged-pool flash decode.

``decode_attention``: (B, 1, H, Dh) query + (B, T, Hkv, Dh) caches.
``paged_decode_attention``: (B, 1, H, Dh) query + the LeaseEngine pool's
(n_rows, token_row) view + per-request page tables / lengths + the current
token's fresh (k, v) -- KV never leaves its pool pages, and each call reads
only its layer's K and V columns of the pages in use.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import decode_attention_grouped, paged_decode_attention_grouped


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_len, *, block_k: int = 512,
                     interpret: bool = False):
    b, one, h, dh = q.shape
    _, t, hkv, _ = k_cache.shape
    g = h // hkv
    scale = dh ** -0.5
    pad = (-dh) % 128
    if pad:
        padw = [(0, 0)] * 3 + [(0, pad)]
        q, k_cache, v_cache = (jnp.pad(a, padw) for a in (q, k_cache, v_cache))
    qg = q.reshape(b, h, -1).reshape(b, hkv, g, q.shape[-1]) \
        .reshape(b * hkv, g, q.shape[-1])
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, t, k_cache.shape[-1])
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, t, v_cache.shape[-1])
    out = decode_attention_grouped(qg, kt, vt, kv_len, scale=scale,
                                   block_k=block_k, interpret=interpret)
    out = out.reshape(b, hkv, g, -1).reshape(b, h, -1)[..., :dh]
    return out[:, None].reshape(b, 1, h, dh)


@partial(jax.jit, static_argnames=("chunk", "k_off", "v_off", "hkv",
                                   "pool_off", "interpret"))
def paged_decode_attention(q, cur_k, cur_v, pool_rows, page_rows, lengths,
                           *, chunk: int, k_off: int, v_off: int, hkv: int,
                           pool_off: int = 0, interpret: bool = False):
    """q: (B, 1, H, Dh); cur_k/cur_v: (B, 1, Hkv, Dh) (the decode token's
    fresh KV, already RoPE'd); pool_rows: (n_blocks*chunk, token_row);
    page_rows: (B, P) int32; lengths: (B,) int32.

    ``k_off`` / ``v_off`` are the layer's static column offsets inside its
    cache stack's segment (a stack's rows pack every layer's K then V
    contiguously) and ``pool_off`` is the stack's segment offset inside the
    interleaved multi-pool token row (0 for single-stack families) -- the
    kernel DMAs the (chunk, Hkv*Dh) windows at ``pool_off + k_off`` and
    ``pool_off + v_off`` of each page in use, not the whole row.
    """
    b, one, h, dh = q.shape
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)
    out = paged_decode_attention_grouped(
        qg, cur_k.reshape(b, hkv, dh), cur_v.reshape(b, hkv, dh),
        pool_rows, page_rows, lengths, scale=dh ** -0.5, chunk=chunk,
        k_off=pool_off + k_off, v_off=pool_off + v_off, interpret=interpret)
    return out.reshape(b, 1, h, dh)
