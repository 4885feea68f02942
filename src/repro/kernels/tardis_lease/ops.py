"""Public wrappers: flat block tables of any size, masked ops, jump-ahead.

``masked_lease_check`` / ``write_advance`` are the two transitions the
:class:`repro.core.lease_engine.LeaseEngine` executes on device;
``lease_check`` is the whole-table convenience form (mask = all blocks).
``masked_lease_check_many`` is the per-wave batched form (G mask rows, one
kernel pass) and ``gather_blocks`` materializes paged-KV pool rows for a
set of leased block ids.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import (LANES, advance_table, gather_rows, lease_table,
                     lease_table_many, rowmax_table, scatter_rows)

# Table block height: Mosaic tiles int32 as (8, 128), so every table is
# padded to a multiple of ROWS rows and streams in (ROWS, LANES) blocks.
ROWS = 8


def _table_pad(n: int) -> int:
    """Padding that makes an (n,) table a whole number of (ROWS, LANES)
    blocks; padded lanes carry mask == 0, so no kernel result changes."""
    return (-n) % (ROWS * LANES)


def _pad2d(x, pad, fill=0):
    return jnp.pad(x, (0, pad), constant_values=fill).reshape(-1, LANES)


@partial(jax.jit, static_argnames=("interpret",))
def masked_lease_check(wts, rts, req_wts, mask, pts, lease,
                       interpret: bool = False):
    """Lease-check / renew / extend the blocks selected by ``mask``.

    wts/rts/req_wts/mask: flat (N,) int32 tables; ``lease`` is a scalar or
    a per-block (N,) vector (the Tardis 2.0 predicted-lease path).  Returns
    dict with per-block ``new_rts`` (extended only where masked),
    ``renew_ok`` / ``expired`` flags (False outside the mask), the writer's
    jump-ahead operand ``write_ts`` = max(masked rts) + 1, and the reader's
    program timestamp after consuming every masked readable block,
    ``new_pts``.
    """
    n = wts.shape[0]
    pad = _table_pad(n)
    wts2 = _pad2d(wts, pad)
    rts2 = _pad2d(rts, pad)
    req2 = _pad2d(req_wts, pad)
    mask2 = _pad2d(mask, pad)          # padding lanes carry mask == 0
    lease2 = jnp.asarray(lease, jnp.int32)
    if lease2.ndim:                    # per-block predicted leases
        lease2 = _pad2d(lease2, pad)
    new_rts, flags, rowmax_rts, rowmax_wts = lease_table(
        wts2, rts2, req2, mask2, pts, lease2,
        block_rows=ROWS, interpret=interpret)
    return {
        "new_rts": new_rts.reshape(-1)[:n],
        "renew_ok": (flags.reshape(-1)[:n] & 1).astype(bool),
        "expired": ((flags.reshape(-1)[:n] >> 1) & 1).astype(bool),
        "write_ts": jnp.max(rowmax_rts) + 1,
        "new_pts": jnp.maximum(jnp.asarray(pts, jnp.int32),
                               jnp.max(rowmax_wts)),
    }


@partial(jax.jit, static_argnames=("interpret",))
def masked_lease_check_many(wts, rts, req_wts, masks, pts_vec, lease,
                            interpret: bool = False):
    """Per-wave batched lease check: G mask rows resolved in one pass.

    wts/rts/req_wts: flat (N,) int32 tables; masks: (G, N) int32 -- one row
    per requester of the wave; pts_vec: (G,) int32 program timestamps;
    ``lease``: scalar or per-block (N,) vector.
    Returns per-block ``new_rts`` (the union of the per-group Table III
    extensions), per-group ``renew_ok`` / ``expired`` flags (G, N) evaluated
    against the pre-call table (the wave's shared snapshot), the writer's
    jump-ahead operand ``write_ts`` over the union mask, and per-group
    reader timestamps ``new_pts`` (G,).
    """
    n = wts.shape[0]
    g = masks.shape[0]
    pad = _table_pad(n)
    wts2 = _pad2d(wts, pad)
    rts2 = _pad2d(rts, pad)
    req2 = _pad2d(req_wts, pad)
    masks2 = jnp.pad(masks, ((0, 0), (0, pad))).reshape(g, -1, LANES)
    lease2 = jnp.asarray(lease, jnp.int32)
    if lease2.ndim:                    # per-block predicted leases
        lease2 = _pad2d(lease2, pad)
    new_rts, flags, rowmax_rts, rowmax_wts = lease_table_many(
        wts2, rts2, req2, masks2, pts_vec, lease2,
        block_rows=ROWS, interpret=interpret)
    flags_flat = flags.reshape(g, -1)[:, :n]
    return {
        "new_rts": new_rts.reshape(-1)[:n],
        "renew_ok": (flags_flat & 1).astype(bool),
        "expired": ((flags_flat >> 1) & 1).astype(bool),
        "write_ts": jnp.max(rowmax_rts) + 1,
        "new_pts": jnp.maximum(jnp.asarray(pts_vec, jnp.int32),
                               jnp.max(rowmax_wts, axis=(1, 2))),
    }


@partial(jax.jit, static_argnames=("interpret",))
def write_advance(wts, rts, mask, pts, interpret: bool = False):
    """Writer jump-ahead over the blocks selected by ``mask``.

    Two kernel passes: the rowmax kernel reduces max(masked rts) per row,
    then the advance kernel sets ``wts = rts = ts`` on every masked block
    with ``ts = max(pts, max(masked rts) + 1)`` (Table I store rule).
    Returns (new_wts, new_rts, ts), all int32.
    """
    n = wts.shape[0]
    pad = _table_pad(n)
    wts2 = _pad2d(wts, pad)
    rts2 = _pad2d(rts, pad)
    mask2 = _pad2d(mask, pad)
    rowmax_rts = rowmax_table(rts2, mask2, block_rows=ROWS,
                              interpret=interpret)
    ts = jnp.maximum(jnp.asarray(pts, jnp.int32), jnp.max(rowmax_rts) + 1)
    new_wts, new_rts = advance_table(wts2, rts2, mask2, ts, block_rows=ROWS,
                                     interpret=interpret)
    return new_wts.reshape(-1)[:n], new_rts.reshape(-1)[:n], ts


@partial(jax.jit, static_argnames=("interpret",))
def lease_check(wts, rts, req_wts, pts, lease, interpret: bool = False):
    """Whole-table form: every block selected (mask of ones)."""
    mask = jnp.ones_like(wts)
    return masked_lease_check(wts, rts, req_wts, mask, pts, lease,
                              interpret=interpret)


@partial(jax.jit, static_argnames=("col_lo", "width", "interpret"))
def gather_blocks(pool, idx, col_lo: int = 0, width: int = None,
                  interpret: bool = False):
    """Materialize pool rows for leased block ids: pool (N, W), idx (n,).

    ``col_lo``/``width`` select one named stack's LANES-aligned column
    window of an interleaved multi-pool token row (default: the whole row).
    """
    return gather_rows(pool, idx, col_lo=col_lo, width=width,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("col_lo", "width", "interpret"),
         donate_argnums=(0,))
def append_rows(pool, idx, rows, col_lo: int = 0, width: int = None,
                interpret: bool = False):
    """Scatter updated rows into ``pool[idx, col_lo:...]`` device-side (the
    append-KV path).

    pool (N, W); idx (n,) int32; rows (n, w) right-padded with zeros to
    ``width`` (default: the pool's full row width).  ``col_lo`` places the
    window at a stack's segment of an interleaved multi-pool token row --
    columns outside [col_lo, col_lo + width) keep their bits, and an id off
    the pool (negative, or at or past its end) writes nothing.  Returns the
    updated pool; the input pool buffer is donated/aliased so no full-pool
    copy happens on TPU.
    """
    if width is None:
        width = pool.shape[1] - col_lo
    w = rows.shape[1]
    if w != width:
        rows = jnp.pad(rows, ((0, 0), (0, width - w)))
    return scatter_rows(pool, idx, rows.astype(pool.dtype), col_lo=col_lo,
                        interpret=interpret)
