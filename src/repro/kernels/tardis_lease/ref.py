"""Oracle: the same rules straight from repro.core.protocol.

These are the differential-test references for the kernels: the masked
forms compose the scalar Table I-III rules (``lease_extend``, ``renewable``,
``shared_expired``) with the batched helpers ``batched_read_check`` /
``batched_write_advance`` exactly as the kernel does, so outputs must be
bit-identical int32.
"""
import jax.numpy as jnp

from ...core import protocol as P


def masked_lease_check_ref(wts, rts, req_wts, mask, pts, lease):
    mask = mask != 0
    # batched_read_check on the masked view: unselected blocks look like
    # expired empty lines (rts = -1) so they are neither readable nor consumed.
    readable, new_pts = P.batched_read_check(
        pts, jnp.where(mask, wts, 0), jnp.where(mask, rts, -1))
    del readable
    return {
        "new_rts": jnp.where(mask, P.lease_extend(wts, rts, pts, lease), rts),
        "renew_ok": mask & P.renewable(req_wts, wts),
        "expired": mask & P.shared_expired(pts, rts),
        "write_ts": jnp.max(jnp.where(mask, rts, -1), initial=-1) + 1,
        "new_pts": new_pts,
    }


def masked_lease_check_many_ref(wts, rts, req_wts, masks, pts_vec, lease):
    """Oracle for the multi-row mask path: the per-group scalar rules
    composed exactly as the batched kernel does -- flags and consumed maxima
    against the pre-call table, rts extended by the union (max over groups)
    of the per-group Table III extensions."""
    masks = masks != 0
    union = jnp.any(masks, axis=0)
    new_rts = rts
    expired, renew_ok, new_pts = [], [], []
    for g in range(masks.shape[0]):
        m, pts = masks[g], pts_vec[g]
        expired.append(m & P.shared_expired(pts, rts))
        renew_ok.append(m & P.renewable(req_wts, wts))
        _, npts = P.batched_read_check(
            pts, jnp.where(m, wts, 0), jnp.where(m, rts, -1))
        new_pts.append(npts)
        new_rts = jnp.where(
            m, jnp.maximum(new_rts, P.lease_extend(wts, rts, pts, lease)),
            new_rts)
    return {
        "new_rts": new_rts,
        "renew_ok": jnp.stack(renew_ok),
        "expired": jnp.stack(expired),
        "write_ts": jnp.max(jnp.where(union, rts, -1), initial=-1) + 1,
        "new_pts": jnp.stack(new_pts),
    }


def write_advance_ref(wts, rts, mask, pts):
    mask = mask != 0
    new_pts, w, r = P.batched_write_advance(pts, rts, mask)
    return jnp.where(mask, w, wts), jnp.where(mask, r, rts), new_pts


def lease_check_ref(wts, rts, req_wts, pts, lease):
    return masked_lease_check_ref(wts, rts, req_wts, jnp.ones_like(wts),
                                  pts, lease)


def append_rows_ref(pool, idx, rows, col_lo: int = 0, width: int = None):
    """Oracle for the append-KV scatter: pool.at[idx, window].set(rows) with
    rows right-padded to the window width (last write wins on duplicates);
    ``col_lo``/``width`` select a stack's column window of an interleaved
    multi-pool token row (default: the whole row).  An id off the pool,
    negative or at or past its end, writes nothing."""
    if width is None:
        width = pool.shape[1] - col_lo
    w = rows.shape[1]
    if w != width:
        rows = jnp.pad(rows, ((0, 0), (0, width - w)))
    idx = jnp.asarray(idx)
    idx = jnp.where(idx < 0, pool.shape[0], idx)
    return pool.at[idx, col_lo:col_lo + width].set(
        rows.astype(pool.dtype), mode="drop")


def gather_blocks_ref(pool, idx, col_lo: int = 0, width: int = None):
    """Oracle for the paged-KV gather with a stack column window."""
    if width is None:
        width = pool.shape[1] - col_lo
    return pool[jnp.asarray(idx), col_lo:col_lo + width]
