"""Flash-decode Pallas kernels: one query token vs. a long KV cache.

``_decode_kernel`` is the dense-cache path -- grid (batch * kv_heads,
num_kv_blocks), the kv dimension sequential, with the GQA group's
(m, l, acc) accumulators in VMEM scratch (split-S partial softmax).
``kv_len`` is a *dynamic* scalar (continuous batching!) delivered through
scalar prefetch so block masking needs no recompilation.

``_paged_decode_kernel`` is the paged-pool path: each request's KV lives in
LeaseEngine pool pages (one lane-padded row per token, all layers packed),
named by a per-request page-table row of block ids.  The scalar-prefetched
page tables drive the K/V input index maps -- the same DMA trick as the
lease engine's ``_gather_kernel`` -- so grid step (b, j) streams request
b's j-th page straight from the pool with no host round trip and no
materialized per-request cache.  The pool enters twice, as a K and a V
operand whose (chunk, hk*dh) blocks are this layer's K and V column
windows of the page, so a step moves one layer's K/V and not the whole
token row.  Per-request ``lengths`` (also prefetched) mask the ragged
tail, and clamp the page index to the request's last page in use: the
steps past it name the block already fetched, so the pipeline issues no
DMA for the reserved pages not yet written.  The current decode token's
fresh (k, v) ride in as a separate operand folded into the accumulators
at j == 0, which keeps the append-then-attend ordering of the dense path
without re-reading the row the step just wrote.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int, num_kv: int):
    ik = pl.program_id(1)
    kv_len = len_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (g, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (g, bk)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(kpos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(m_new <= NEG_INF, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))

    @pl.when(ik == num_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_grouped(q, k, v, kv_len, *, scale: float,
                             block_k: int = 512, interpret: bool = False):
    """q: (B*Hkv, G, Dh); k, v: (B*Hkv, T, Dh); kv_len: () int32."""
    bh, g, dh = q.shape
    _, t, _ = k.shape
    block_k = min(block_k, t)
    assert t % block_k == 0
    num_kv = t // block_k
    grid = (bh, num_kv)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                          num_kv=num_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, g, dh), lambda bh_, ik, _s: (bh_, 0, 0)),
                pl.BlockSpec((1, block_k, dh),
                             lambda bh_, ik, _s: (bh_, ik, 0)),
                pl.BlockSpec((1, block_k, dh),
                             lambda bh_, ik, _s: (bh_, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, dh),
                                   lambda bh_, ik, _s: (bh_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bh, g, dh), q.dtype),
        interpret=interpret,
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), q, k, v)


def _paged_decode_kernel(scalars_ref, q_ref, cur_k_ref, cur_v_ref, k_ref,
                         v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                         chunk: int, k_lo: int, v_lo: int, hkd: int,
                         num_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    kv_len = scalars_ref[b]                           # this request's tokens

    # block-diagonal query: row (h, g) holds q[h, g] in head h's dh columns
    # and zeros elsewhere, so one 2-D matmul against a page's (chunk,
    # hk*dh) K slice scores every head against its own KV head only
    q = q_ref[0].astype(jnp.float32)                  # (hk*g, hk*dh)

    @pl.when(j == 0)
    def _init():
        # fold the CURRENT token (always attended, position == kv_len)
        # into fresh accumulators before any pool page streams in
        ck = cur_k_ref[0].astype(jnp.float32)         # (1, hk*dh)
        cv = cur_v_ref[0].astype(jnp.float32)
        s0 = jnp.sum(q * ck, axis=1, keepdims=True) * scale
        m_scr[...] = s0                               # (hk*g, 1)
        l_scr[...] = jnp.ones_like(s0)
        acc_scr[...] = jnp.broadcast_to(cv, acc_scr.shape)

    @pl.when(j * chunk < kv_len)                      # a page in use
    def _page():
        # (chunk, hk*dh): the whole window block, or the layer's lane-
        # aligned columns of a whole-row block
        k = k_ref[:, k_lo:k_lo + hkd].astype(jnp.float32)
        v = v_ref[:, v_lo:v_lo + hkd].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        kpos = j * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)      # (hk*g, chunk)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(m_new <= NEG_INF, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        # (hk*g, hk*dh): row (h, g) is meaningful only in head h's columns
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))

    @pl.when(j == num_pages - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_block_width(hkd: int, k_off: int, v_off: int,
                      token_row: int) -> int:
    """Width of the pool blocks the paged kernel DMAs: the layer's own
    ``hkd``-wide K and V column windows where both are whole lane-aligned
    blocks of the row (every served configuration: hk*dh is a multiple of
    128 and the stacks' K/V offsets multiples of hk*dh), else the whole
    token row, DMA'd once per operand and sliced in VMEM (only shapes no
    configuration serves)."""
    if hkd % 128 == 0 and k_off % hkd == 0 and v_off % hkd == 0:
        return hkd
    return token_row


def paged_decode_attention_grouped(q, cur_k, cur_v, pool_rows, page_rows,
                                   lengths, *, scale: float, chunk: int,
                                   k_off: int, v_off: int,
                                   interpret: bool = False):
    """q: (B, Hkv, G, Dh); cur_k/cur_v: (B, Hkv, Dh) -- the token being
    decoded; pool_rows: (n_blocks*chunk, token_row) engine pool view;
    page_rows: (B, P) int32 page tables (entries past a request's pages
    must be clamped valid); lengths: (B,) int32 tokens already in pages.

    Attends over [pool tokens 0..lengths[b]) ; current token] per request.
    The kernel works on 2-D tiles only: the query enters block-diagonal
    (B, Hkv*G, Hkv*Dh), K and V are the page's (chunk, Hkv*Dh) column
    windows (``paged_block_width``), and each head's output is read back
    off the diagonal here.  Only pages holding tokens below ``lengths[b]``
    are read.
    """
    b, hk, g, dh = q.shape
    hkd = hk * dh
    num_pages = page_rows.shape[1]
    w = paged_block_width(hkd, k_off, v_off, pool_rows.shape[1])
    own_head = jnp.eye(hk, dtype=bool)[:, None, :, None]      # (hk,1,hk,1)
    q_bd = jnp.where(own_head, q[:, :, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(b, hk * g, hkd)
    scalars = jnp.concatenate([
        jnp.asarray(lengths, jnp.int32).reshape(-1),
        jnp.asarray(page_rows, jnp.int32).reshape(-1)])

    def pool_block(col):
        # the page table drives the pool DMA: page j of request bb, clamped
        # to its last page in use so later steps re-name the fetched block
        def index(bb, j, s):
            last = jax.lax.div(jnp.maximum(s[bb] - 1, 0), chunk)
            return s[b + bb * num_pages + jnp.minimum(j, last)], col
        return pl.BlockSpec((chunk, w), index)

    grid = (b, num_pages)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, chunk=chunk,
                          k_lo=k_off % w, v_lo=v_off % w, hkd=hkd,
                          num_pages=num_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, hk * g, hkd), lambda bb, j, _s: (bb, 0, 0)),
                pl.BlockSpec((1, 1, hkd), lambda bb, j, _s: (bb, 0, 0)),
                pl.BlockSpec((1, 1, hkd), lambda bb, j, _s: (bb, 0, 0)),
                pool_block(k_off // w),
                pool_block(v_off // w),
            ],
            out_specs=pl.BlockSpec((1, hk * g, hkd),
                                   lambda bb, j, _s: (bb, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hk * g, 1), jnp.float32),
                pltpu.VMEM((hk * g, 1), jnp.float32),
                pltpu.VMEM((hk * g, hkd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hk * g, hkd), q.dtype),
        interpret=interpret,
    )(scalars, q_bd, cur_k.reshape(b, 1, hkd), cur_v.reshape(b, 1, hkd),
      pool_rows, pool_rows)
    # row (h, g) of head h's column block: the diagonal of (hk, hk)
    out = jnp.diagonal(out.reshape(b, hk, g, hk, dh), axis1=1, axis2=3)
    return out.transpose(0, 3, 1, 2)                  # (b, hk, g, dh)
