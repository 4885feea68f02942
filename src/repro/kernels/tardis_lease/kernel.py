"""Batched Tardis timestamp-manager rules as Pallas TPU kernels.

The TPU has no per-cacheline FSM, so the protocol's hot metadata path -- a
timestamp manager serving thousands of lease checks / renewals / write
jump-aheads against a block table -- becomes a lane-vectorized array program
(DESIGN.md section 2.3).  Two kernels cover Tables I-III for a (rows, 128)
block table, restricted to the blocks selected by an int32 ``mask``:

``_lease_kernel`` (load / renew / SH_REQ path), per masked block:

  * expired     = pts > rts                      (Table II, shared line check)
  * renew_ok    = req_wts == wts                 (data-less RENEW_REP)
  * new_rts     = max(rts, wts + lease, pts + lease)   (Table III, SH_REQ)
  * row max of masked rts                        (writer jump-ahead reduce)
  * row max of consumed wts (mask & ~expired)    (reader pts advance,
                                                  Table I load: pts<-max(pts,wts))

``_advance_kernel`` (store / jump-ahead path): given the writer's new
timestamp ``ts = max(pts, max(masked rts) + 1)`` computed from the lease
pass's row maxima, sets ``wts = rts = ts`` on every masked block (Table I
store rule: the new version is valid exactly from the jump-ahead instant).

``_lease_many_kernel`` is the **multi-row mask path**: a wave of G
requesters, each selecting its own subset of the table (mask row g) at its
own program timestamp ``pts_g``, resolved in ONE pass.  Per-group flags and
pts-advance operands come back stacked on a leading G axis; the rts
extension is the union over selecting groups (``max_g`` of the per-group
Table III extensions -- order-independent, so the batched result is
bit-identical to issuing the G lease passes back to back).  Flags are
evaluated against the *pre-call* table, which is the wave semantics: every
requester of the wave observes the same table snapshot.

``_gather_kernel`` is the paged-KV materialization path: scalar-prefetched
row ids address the pool in HBM, so leased KV chunks stream from the pool
into a replica's cache without a host round-trip.  A DMA moves whole
memory tiles of rows (16 rows of bf16), so the gather and the scatter
(``scatter_rows``) reach a single token row through the aligned tile that
holds it.  Both take a **column window** -- a LANES-aligned
``col_lo``/``width`` pair that is the DMA's column slice -- so a
multi-pool engine (one named KV pool per cache stack, interleaved inside
each token row) can stream or append a single stack's segment without
touching its neighbors' bits.

pts/lease (and ts for the advance pass) arrive via scalar prefetch so a
serving engine can stream tables through the same compiled kernels; a
Tardis 2.0 predicted (per-block) lease instead rides as one more tensor
input on the same BlockSpec as the tables -- static policies keep the
scalar path and pay nothing for the feature.
Unselected blocks pass through untouched, which is also how ragged tables
are handled: the padding lanes simply carry mask == 0.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _lease_step(pts, lease, wts_ref, rts_ref, reqwts_ref, mask_ref,
                new_rts_ref, flags_ref, rowmax_rts_ref, rowmax_wts_ref):
    wts = wts_ref[...]
    rts = rts_ref[...]
    req = reqwts_ref[...]
    mask = mask_ref[...] != 0

    expired = mask & (pts > rts)
    renew_ok = mask & (req == wts)
    ext = jnp.maximum(jnp.maximum(rts, wts + lease), pts + lease)

    new_rts_ref[...] = jnp.where(mask, ext, rts)
    flags_ref[...] = (renew_ok.astype(jnp.int32)
                      | (expired.astype(jnp.int32) << 1))
    # Writer jump-ahead operand: max rts over the selected blocks (pre-extend).
    rowmax_rts_ref[...] = jnp.max(jnp.where(mask, rts, -1), axis=1,
                                  keepdims=True)
    # Reader pts advance operand: max wts over selected *readable* blocks
    # (expired blocks renew first; their wts <= rts < pts cannot raise pts).
    consumed = jnp.where(mask & (pts <= rts), wts, 0)
    rowmax_wts_ref[...] = jnp.max(consumed, axis=1, keepdims=True)


def _lease_kernel(scalars_ref, wts_ref, rts_ref, reqwts_ref, mask_ref,
                  new_rts_ref, flags_ref, rowmax_rts_ref, rowmax_wts_ref):
    # static policy: one lease value rides the scalar prefetch
    _lease_step(scalars_ref[0], scalars_ref[1], wts_ref, rts_ref, reqwts_ref,
                mask_ref, new_rts_ref, flags_ref, rowmax_rts_ref,
                rowmax_wts_ref)


def _lease_pred_kernel(scalars_ref, wts_ref, rts_ref, reqwts_ref, mask_ref,
                       lease_ref, new_rts_ref, flags_ref, rowmax_rts_ref,
                       rowmax_wts_ref):
    # Tardis 2.0 predictor: per-block leases stream as a table input
    _lease_step(scalars_ref[0], lease_ref[...], wts_ref, rts_ref, reqwts_ref,
                mask_ref, new_rts_ref, flags_ref, rowmax_rts_ref,
                rowmax_wts_ref)


def _lease_many_step(lease, pts_at, wts_ref, rts_ref, reqwts_ref, masks_ref,
                     new_rts_ref, flags_ref, rowmax_rts_ref, rowmax_wts_ref):
    wts = wts_ref[...]
    rts = rts_ref[...]
    req = reqwts_ref[...]
    n_groups = masks_ref.shape[0]

    union = jnp.zeros_like(wts)
    new_rts = rts
    for g in range(n_groups):           # static: unrolled over the wave
        pts = pts_at(g)
        mask = masks_ref[g] != 0
        expired = mask & (pts > rts)
        renew_ok = mask & (req == wts)
        ext = jnp.maximum(jnp.maximum(rts, wts + lease), pts + lease)
        new_rts = jnp.where(mask, jnp.maximum(new_rts, ext), new_rts)
        union = jnp.where(mask, 1, union)
        flags_ref[g, ...] = (renew_ok.astype(jnp.int32)
                             | (expired.astype(jnp.int32) << 1))
        consumed = jnp.where(mask & (pts <= rts), wts, 0)
        rowmax_wts_ref[g, ...] = jnp.max(consumed, axis=1, keepdims=True)
    new_rts_ref[...] = new_rts
    rowmax_rts_ref[...] = jnp.max(jnp.where(union != 0, rts, -1), axis=1,
                                  keepdims=True)


def _lease_many_kernel(scalars_ref, wts_ref, rts_ref, reqwts_ref, masks_ref,
                       new_rts_ref, flags_ref, rowmax_rts_ref,
                       rowmax_wts_ref):
    # static policy: scalars are [lease, pts_0 .. pts_{G-1}]
    _lease_many_step(scalars_ref[0], lambda g: scalars_ref[1 + g], wts_ref,
                     rts_ref, reqwts_ref, masks_ref, new_rts_ref, flags_ref,
                     rowmax_rts_ref, rowmax_wts_ref)


def _lease_many_pred_kernel(scalars_ref, wts_ref, rts_ref, reqwts_ref,
                            masks_ref, lease_ref, new_rts_ref, flags_ref,
                            rowmax_rts_ref, rowmax_wts_ref):
    # Tardis 2.0 predictor: scalars are pts_0 .. pts_{G-1}, lease is a table
    _lease_many_step(lease_ref[...], lambda g: scalars_ref[g], wts_ref,
                     rts_ref, reqwts_ref, masks_ref, new_rts_ref, flags_ref,
                     rowmax_rts_ref, rowmax_wts_ref)


def _rowmax_kernel(scalars_ref, rts_ref, mask_ref, rowmax_rts_ref):
    del scalars_ref                     # shared plumbing; no scalars needed
    rts = rts_ref[...]
    mask = mask_ref[...] != 0
    rowmax_rts_ref[...] = jnp.max(jnp.where(mask, rts, -1), axis=1,
                                  keepdims=True)


def _advance_kernel(scalars_ref, wts_ref, rts_ref, mask_ref,
                    new_wts_ref, new_rts_ref):
    ts = scalars_ref[0]
    mask = mask_ref[...] != 0
    new_wts_ref[...] = jnp.where(mask, ts, wts_ref[...])
    new_rts_ref[...] = jnp.where(mask, ts, rts_ref[...])


def _grid_call(kernel, inputs, out_lanes, block_rows, interpret, scalars):
    """Shared pallas_call plumbing for the (rows, LANES) table kernels."""
    r = inputs[0].shape[0]
    block_rows = min(block_rows, r)
    assert r % block_rows == 0
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i, _s: (i, 0))
    out_specs = [
        spec if lanes == LANES
        else pl.BlockSpec((block_rows, lanes), lambda i, _s: (i, 0))
        for lanes in out_lanes]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[spec] * len(inputs),
            out_specs=out_specs),
        out_shape=[jax.ShapeDtypeStruct((r, lanes), jnp.int32)
                   for lanes in out_lanes],
        interpret=interpret,
    )(scalars, *inputs)


def lease_table(wts, rts, req_wts, mask, pts, lease, *, block_rows: int = 8,
                interpret: bool = False):
    """wts/rts/req_wts/mask: (R, 128) int32; pts: scalar.

    ``lease`` is a scalar (static policy -- rides the scalar prefetch, no
    extra table stream) or a per-block (R, 128) tensor (the Tardis 2.0
    predicted-lease path).  Returns (new_rts (R,128), flags (R,128),
    rowmax_rts (R,1), rowmax_wts (R,1)); flags bit0 = renew_ok, bit1 =
    expired, both zero outside the mask.
    """
    assert wts.shape[1] == LANES, wts.shape
    lease = jnp.asarray(lease, jnp.int32)
    if lease.ndim == 0:
        scalars = jnp.stack([jnp.asarray(pts, jnp.int32), lease])
        return _grid_call(_lease_kernel, (wts, rts, req_wts, mask),
                          (LANES, LANES, 1, 1), block_rows, interpret,
                          scalars)
    assert lease.shape == wts.shape, (lease.shape, wts.shape)
    scalars = jnp.stack([jnp.asarray(pts, jnp.int32)])
    return _grid_call(_lease_pred_kernel, (wts, rts, req_wts, mask, lease),
                      (LANES, LANES, 1, 1), block_rows, interpret, scalars)


def rowmax_table(rts, mask, *, block_rows: int = 8,
                 interpret: bool = False):
    """max(masked rts) per row -- the writer jump-ahead operand.

    The write path needs only this reduction from the lease pass, so it
    gets a dedicated 2-input/1-output kernel instead of streaming the
    full 5-input lease kernel (whose per-block lease tensor the jump-ahead
    never reads)."""
    assert rts.shape[1] == LANES, rts.shape
    scalars = jnp.zeros((1,), jnp.int32)
    (out,) = _grid_call(_rowmax_kernel, (rts, mask), (1,),
                        block_rows, interpret, scalars)
    return out


def advance_table(wts, rts, mask, ts, *, block_rows: int = 8,
                  interpret: bool = False):
    """Set wts = rts = ts on every masked block; returns (new_wts, new_rts)."""
    assert wts.shape[1] == LANES, wts.shape
    scalars = jnp.stack([jnp.asarray(ts, jnp.int32)])
    return _grid_call(_advance_kernel, (wts, rts, mask),
                      (LANES, LANES), block_rows, interpret, scalars)


def lease_table_many(wts, rts, req_wts, masks, pts_vec, lease, *,
                     block_rows: int = 8, interpret: bool = False):
    """Multi-row mask path: one pass over G per-group masks.

    wts/rts/req_wts: (R, 128) int32; masks: (G, R, 128) int32;
    pts_vec: (G,) int32 per-group program timestamps; lease: scalar
    (static policy -- rides the scalar prefetch) or (R, 128) int32
    per-block leases (the Tardis 2.0 predicted-lease path).

    Returns (new_rts (R,128) -- union extension, flags (G,R,128) -- bit0
    renew_ok / bit1 expired per group vs the pre-call table, rowmax_rts
    (R,1) over the union mask, rowmax_wts (G,R,1) per-group consumed
    maxima for the readers' pts advance).
    """
    assert wts.shape[1] == LANES, wts.shape
    g, r = masks.shape[0], wts.shape[0]
    assert masks.shape == (g, r, LANES), masks.shape
    lease = jnp.asarray(lease, jnp.int32)
    if lease.ndim == 0:
        kernel = _lease_many_kernel
        scalars = jnp.concatenate([lease[None],
                                   jnp.asarray(pts_vec, jnp.int32)])
        tables = (wts, rts, req_wts, masks)
    else:
        assert lease.shape == wts.shape, (lease.shape, wts.shape)
        kernel = _lease_many_pred_kernel
        scalars = jnp.asarray(pts_vec, jnp.int32)
        tables = (wts, rts, req_wts, masks, lease)
    block_rows = min(block_rows, r)
    assert r % block_rows == 0
    grid = (r // block_rows,)
    spec2 = pl.BlockSpec((block_rows, LANES), lambda i, _s: (i, 0))
    spec3 = pl.BlockSpec((g, block_rows, LANES), lambda i, _s: (0, i, 0))
    in_specs = [spec2, spec2, spec2, spec3] + [spec2] * (len(tables) - 4)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                spec2,                                        # new_rts
                spec3,                                        # flags
                pl.BlockSpec((block_rows, 1), lambda i, _s: (i, 0)),
                pl.BlockSpec((g, block_rows, 1), lambda i, _s: (0, i, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((r, LANES), jnp.int32),
            jax.ShapeDtypeStruct((g, r, LANES), jnp.int32),
            jax.ShapeDtypeStruct((r, 1), jnp.int32),
            jax.ShapeDtypeStruct((g, r, 1), jnp.int32),
        ],
        interpret=interpret,
    )(scalars, *tables)


def _row_tile(pool) -> int:
    """Rows per pool DMA: the height of the chip's (8, 128) 32-bit memory
    tile in the pool's dtype (16 rows of bf16), cut to a divisor of the
    pool's row count.  A DMA may only move whole tiles of rows, so the
    gather and the scatter address a single token row through the aligned
    tile that holds it.  Serving pools are whole tiles (``n_blocks *
    chunk`` rows with a 16-token chunk); the cut only matters for the
    small pools that run in interpret mode."""
    return math.gcd(8 * max(1, 4 // pool.dtype.itemsize), pool.shape[0])


def _pick_row(block, r):
    """Row ``r`` (traced) of a (tile, w) block, as (1, w), bit for bit.

    Mosaic has no single-row load at a dynamic offset for packed dtypes,
    so the row is selected with an iota mask and a max over rows in which
    every other entry is -inf.  The row comes back in float32, which holds
    every value of a float32, bf16 or float16 pool exactly."""
    hit = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) == r
    picked = jnp.where(hit, block.astype(jnp.float32), -jnp.inf)
    return jnp.max(picked, axis=0, keepdims=True)


def _window(pool_hbm, row, tile: int, col_lo: int, width: int):
    """The aligned (tile, width) DMA window of the pool holding ``row``."""
    start = pl.multiple_of(row - row % tile, tile)
    return pool_hbm.at[pl.ds(start, tile), pl.ds(col_lo, width)]


def _gather_kernel(idx_ref, pool_hbm, out_ref, buf, sem, *, tile: int,
                   col_lo: int, width: int):
    i = pl.program_id(0)
    out = jnp.zeros(out_ref.shape, jnp.float32)
    for k in range(tile):                # static: one output tile per step
        row = idx_ref[i * tile + k]
        copy = pltpu.make_async_copy(
            _window(pool_hbm, row, tile, col_lo, width), buf, sem)
        copy.start()
        copy.wait()
        hit = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) == k
        out = jnp.where(hit, _pick_row(buf[...], row % tile), out)
    out_ref[...] = out.astype(out_ref.dtype)


def _scatter_kernel(idx_ref, rows_ref, pool_hbm, out_hbm, buf, sem, *,
                    tile: int, col_lo: int, width: int, n_rows: int):
    del pool_hbm                         # aliased to out_hbm
    i = pl.program_id(0)
    row = idx_ref[i]

    @pl.when((row >= 0) & (row < n_rows))   # an id off the pool: no write
    def _write():
        window = _window(out_hbm, row, tile, col_lo, width)
        load = pltpu.make_async_copy(window, buf, sem)
        load.start()
        load.wait()
        new = _pick_row(rows_ref[...], i % tile).astype(buf.dtype)
        hit = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 0) == row % tile
        buf[...] = jnp.where(hit, new, buf[...])
        store = pltpu.make_async_copy(buf, window, sem)
        store.start()
        store.wait()


def scatter_rows(pool, idx, rows, *, col_lo: int = 0,
                 interpret: bool = False):
    """Scatter ``rows`` into ``pool[idx, col_lo:col_lo+w]``: the append-KV
    path.

    pool (N, W), idx (n,) int32, rows (n, w) with ``col_lo + w <= W``; the
    window [col_lo, col_lo + w) is LANES-aligned (the LeaseEngine pads
    every stack's token-row segment to LANES).  The pool stays in HBM,
    aliased input->output: grid step i reads the aligned tile of rows that
    holds row ``idx[i]``, replaces that one row's window, and writes the
    tile back, so every untouched row -- and every column outside the
    window -- keeps its bits.  A decoded token's KV lands in its page
    without a host round trip, and a per-stack append touches only that
    stack's segment of the interleaved token row.  Each step waits for its
    write before the next step reads, so rows listed twice keep the last
    write, and rows that share a tile all land.  An id at or past the
    pool's end is skipped, as XLA's scatter drops it, and so is a negative
    id (a prefill bucket's pad rows past its prompt).
    """
    n, width = rows.shape
    assert col_lo % LANES == 0 and width % LANES == 0, (col_lo, width)
    tile = _row_tile(pool)
    rows = jnp.pad(rows, ((0, (-n) % tile), (0, 0)))
    return pl.pallas_call(
        functools.partial(_scatter_kernel, tile=tile, col_lo=col_lo,
                          width=width, n_rows=pool.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((tile, width), lambda i, _idx: (i // tile, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tile, width), pool.dtype),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},       # (scalars, rows, POOL) -> out
        interpret=interpret,
    )(jnp.asarray(idx, jnp.int32), rows, pool)


def gather_rows(pool, idx, *, col_lo: int = 0, width: int = None,
                interpret: bool = False):
    """Gather ``pool[idx, col_lo:col_lo+width]`` on device: pool (N, W),
    idx (n,) int32.

    The scalar-prefetched ids address the pool in HBM: for each id the
    kernel DMAs the aligned tile of rows that holds it and keeps that one
    row, filling the output a tile of rows per grid step -- the paged-KV
    materialization path of the serving engine.  ``col_lo``/``width`` name
    a LANES-aligned column window (one stack's segment of an interleaved
    multi-pool token row); the default gathers the whole row.
    """
    n = idx.shape[0]
    if width is None:
        width = pool.shape[1] - col_lo
    assert col_lo % LANES == 0 and width % LANES == 0, (col_lo, width)
    tile = _row_tile(pool)
    idx = jnp.pad(jnp.asarray(idx, jnp.int32), (0, (-n) % tile))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile, col_lo=col_lo,
                          width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(idx.shape[0] // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, width), lambda i, _idx: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tile, width), pool.dtype),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], width), pool.dtype),
        interpret=interpret,
    )(idx, pool)
    return out[:n]
