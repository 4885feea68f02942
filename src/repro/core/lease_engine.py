"""LeaseEngine: the single device-backed implementation of the lease rules.

The repo used to carry three divergent copies of the paper's Tables I-III --
scalar jnp rules in :mod:`repro.core.protocol`, a numpy ``BlockTable`` mirror
in :mod:`repro.core.store`, and an orphaned Pallas kernel under
``repro.kernels.tardis_lease``.  This module collapses them into one
subsystem:

  * the **Pallas kernel** executes every read/renew/write-jump-ahead
    transition against device-resident int32 ``(wts, rts)`` block tables
    (interpret-mode fallback off-TPU),
  * the scalar :mod:`repro.core.protocol` rules remain the differential-test
    oracle (``kernels/tardis_lease/ref.py``),
  * the numpy mirror survives only behind ``backend="numpy"`` so tests can
    diff the kernel against it bit-for-bit.

Timestamps are int32 logical counters guarded by a ``ts_bits`` wraparound
rebase (paper section IV-B applied manager-side): when any timestamp reaches
``2**ts_bits`` the whole table shifts down by ``2**(ts_bits-1)``
(:func:`repro.core.timestamps.rebase_amount`), clamped at zero -- clamping a
low timestamp up to the new base is the paper's "hypothetical later
write/read of the same value", which never violates SC.  Callers holding a
program timestamp or cached leases apply the same shift (see
:meth:`LeaseEngine.maybe_rebase`).

Traffic is charged in message flits from :data:`repro.core.protocol
.MESSAGE_FLITS` so the engine's ledger matches the simulator's accounting:
a read is SH_REQ per block, answered by RENEW_REP (data-less, the common
case once a reader holds the right version) or SH_REP headers plus payload
flits for ``block_bytes``; a write publishes header + payload flits.

Three extensions make leased blocks carry *real data*, make the wave the
unit of dispatch, and make the pool the only KV substrate decode touches:

  * **paged KV pool(s)** -- when constructed with ``kv_block_shape`` (the
    serving layout is ``(chunk, 2, kv_heads, head_dim)``) or with
    ``kv_pools`` (an ordered mapping of NAMED pools, one per cache stack --
    the MoE serving layout is ``{"dense": (chunk, 2, fd*kv_heads, hd),
    "moe": (chunk, 2, nm*kv_heads, hd)}``) the engine owns a
    device-resident ``(n_blocks*chunk, token_row)`` payload pool alongside
    the ``(wts, rts)`` metadata: each block is ``chunk`` consecutive
    lane-padded TOKEN rows, so a single token is one row of the pool
    (``kv_rows_view``).  With multiple pools the
    token row **interleaves** every stack's segment (each lane-padded, at a
    static ``pool_offset``), so ONE block id leases every stack's payload
    and every transition -- lease, write, eviction, relocation, rebase,
    page alloc/free -- stays a single logical event covering all stacks.
    ``write_kv`` scatters block payloads in (all stacks in one dispatch),
    ``read_kv`` materializes them through the ``tardis_lease`` Pallas
    gather kernel (scalar-prefetched ids drive the DMA index map; a
    ``pool=`` argument gathers one stack's column window without touching
    its neighbors), and a host-side validity bitmap tracks which slots hold
    content for the *current* tag -- ``invalidate_kv`` frees a slot on
    collision eviction with zero messages.  ``maybe_rebase`` shifts
    metadata only: pool contents are timestamps-free and survive any
    rebase untouched.
  * **per-wave batched ops** -- ``read_many`` resolves the reads/renewals
    of a whole wave of requesters in ONE ``masked_lease_check_many`` kernel
    dispatch (the multi-row mask path), and ``write_many`` folds a wave's
    writes into one jump-ahead over the union of their blocks.  With every
    requester at the same program timestamp (the serving case: one logical
    tick per wave) the batched results are bit-identical in ``wts/rts/pts``
    to issuing the per-request ops back to back (``tests/test_litmus.py``).
  * **page allocator + token append** -- block ids in ``[alloc_reserve,
    n_blocks)`` are free-listed decode pages (``alloc_pages`` /
    ``free_pages``; admission control gates on ``free_page_count``), and
    ``append_kv`` scatters single-token rows into their page slots through
    the ``tardis_lease`` scatter kernel (ids drive the *output* index map
    with in/out aliasing) -- the serving engine's continuous-batching
    decode runs entirely against ``kv_rows_view`` and writes back with
    ``set_kv_rows``.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from functools import partial
from typing import Dict, List, Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import protocol, timestamps
from .policy import CoherencePolicy
from ..kernels.tardis_lease import ops as lease_ops


@jax.jit
def _gather4(a, b, c, d, idx):
    """One dispatch to slice the per-idx results out of full-table arrays
    (ship len(idx) entries to host, not the whole block table)."""
    return a[idx], b[idx], c[idx], d[idx]


@jax.jit
def _gather_many(expired, renew_ok, wts, rts, idx):
    """read_many's per-union-block slice: flags are (G, N), tables (N,)."""
    return expired[:, idx], renew_ok[:, idx], wts[idx], rts[idx]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(pool, idx, rows):
    """In-place pool update (donated buffer: no full-pool copy on TPU)."""
    return pool.at[idx].set(rows.astype(pool.dtype))


@dataclasses.dataclass
class LeaseStats:
    reads: int = 0               # blocks served through read()/renew
    writes: int = 0              # blocks written through write()
    read_ops: int = 0
    write_ops: int = 0
    expired: int = 0             # blocks whose lease had run out at read
    renewals: int = 0            # reads where the requester held a copy
    data_less: int = 0           # renewals answered RENEW_REP (no payload)
    payload_transfers: int = 0   # blocks answered SH_REP with data
    payload_bytes: int = 0
    flits: int = 0               # total message flits incl. headers
    rebases: int = 0
    kv_blocks_written: int = 0   # payload blocks scattered into the pool
    kv_blocks_read: int = 0      # payload blocks gathered out of the pool
    kv_evictions: int = 0        # pool slots freed by invalidate_kv
    kv_tokens_appended: int = 0  # single token rows appended into pages
    pages_allocated: int = 0     # free-list pops (decode page churn)
    pages_freed: int = 0         # free-list pushes
    pred_grows: int = 0          # predictor: leases grown (wasted renewal)
    pred_shrinks: int = 0        # predictor: leases shrunk (write hit)
    # per-stack occupancy: token rows appended into each named pool's
    # segment (a full-row append feeds every stack at once)
    kv_pool_tokens: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def wire_bytes(self) -> int:
        return self.flits * protocol.FLIT_BYTES


@dataclasses.dataclass
class ReadResult:
    """Per-block outcome of a batched read/renew, aligned with ``idx``."""
    expired: np.ndarray          # bool: lease had run out (renewal happened)
    renew_ok: np.ndarray         # bool: requester's version matched (no data)
    wts: np.ndarray              # int32 block versions (unchanged by a read)
    rts: np.ndarray              # int32 extended leases
    new_pts: int                 # reader's program ts after consuming blocks


@dataclasses.dataclass
class ReadManyResult:
    """Outcome of a per-wave batched read: one kernel dispatch for G groups.

    ``union_idx`` is the sorted union of the groups' block ids; ``wts`` /
    ``rts`` align with it.  ``expired`` / ``renew_ok`` are (G, len(union))
    per-group flags evaluated against the pre-call table (the wave's shared
    snapshot; False outside a group's own blocks) and ``new_pts`` is the
    (G,) per-group reader timestamp after consuming its readable blocks.
    """
    union_idx: np.ndarray
    expired: np.ndarray
    renew_ok: np.ndarray
    wts: np.ndarray
    rts: np.ndarray
    new_pts: np.ndarray


class LeaseEngine:
    """Timestamp manager for a table of ``n_blocks`` leased blocks.

    ``backend="pallas"`` keeps the tables as device arrays and runs every
    transition through the ``tardis_lease`` kernels (interpret mode anywhere
    a TPU is absent); ``backend="numpy"`` is the bit-identical host mirror
    kept for differential tests.
    """

    def __init__(self, n_blocks: int, lease: int = 64, *,
                 policy: Optional[CoherencePolicy] = None,
                 backend: str = "pallas", ts_bits: int = 30,
                 block_bytes: int = 0, interpret: Optional[bool] = None,
                 kv_block_shape: Optional[Sequence[int]] = None,
                 kv_pools: Optional[Mapping[str, Sequence[int]]] = None,
                 kv_dtype=jnp.bfloat16, alloc_reserve: int = 0,
                 sanitize: Optional[bool] = None):
        if backend not in ("pallas", "numpy"):
            raise ValueError(f"unknown backend {backend!r}")
        # ``policy`` is the one configuration object (CoherencePolicy);
        # the loose ``lease``/``ts_bits`` kwargs remain as the legacy
        # spelling and fold into a static-SC policy when no policy is given.
        if policy is None:
            policy = CoherencePolicy(lease=int(lease), ts_bits=int(ts_bits))
        self.policy = policy
        self.n_blocks = int(n_blocks)
        self.lease = int(policy.lease)
        self.backend = backend
        self.ts_bits = int(policy.ts_bits)
        self.block_bytes = int(block_bytes)
        # Tardis 2.0 per-block predicted leases (ts DELTAS, so a uniform
        # rebase never touches them); with the predictor off the vector
        # stays pinned at the static lease and the scalar fast path runs.
        self._pred_lease = np.full(self.n_blocks, policy.lease, np.int32)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = bool(interpret)
        if backend == "pallas":
            self._wts = jnp.zeros(self.n_blocks, jnp.int32)
            self._rts = jnp.zeros(self.n_blocks, jnp.int32)
        else:
            self._wts = np.zeros(self.n_blocks, np.int32)
            self._rts = np.zeros(self.n_blocks, np.int32)
        self.ts_shift = 0            # cumulative rebase amount (see above)
        self.stats = LeaseStats()
        # page allocator: block ids in [alloc_reserve, n_blocks) are the
        # allocatable region (decode pages), handed out lowest-id-first;
        # ids below alloc_reserve stay content-addressed (prefix hashing).
        self.alloc_reserve = int(alloc_reserve)
        self._free_pages = list(range(self.n_blocks - 1,
                                      self.alloc_reserve - 1, -1))
        # O(1) membership for the double-free / never-allocated guards
        self._free_set = set(self._free_pages)
        # paged KV payload pool(s): ``chunk`` lane-padded TOKEN rows per
        # block, back to back, so a single decoded token's KV is one row
        # of the (n_blocks*chunk, token_row) pool (the decode kernels'
        # substrate) and a whole block is ``chunk`` consecutive rows (the
        # gather kernel's).  With MULTIPLE named
        # pools (one per cache stack) each token row interleaves every
        # stack's lane-padded segment at a static column offset -- one
        # block id owns every stack's payload, one free list pages them,
        # one lease transition covers them all.  The validity bitmap is
        # host metadata (whether a slot holds content for its current tag),
        # NOT protocol state -- it carries no timestamps and never rebases;
        # it is per BLOCK, not per stack: a block's content is published
        # for every stack at once (write_kv) or for none.
        if kv_pools is not None and kv_block_shape is not None:
            raise ValueError("pass kv_block_shape or kv_pools, not both")
        if kv_pools is None and kv_block_shape is not None:
            kv_pools = {"kv": kv_block_shape}
        self.kv_pools: Optional[Dict[str, tuple]] = (
            {str(k): tuple(int(s) for s in v) for k, v in kv_pools.items()}
            if kv_pools else None)
        # single-pool back-compat alias (None when multi-pool)
        self.kv_block_shape = (next(iter(self.kv_pools.values()))
                               if self.kv_pools and len(self.kv_pools) == 1
                               else None)
        if self.kv_pools:
            chunks = {s[0] for s in self.kv_pools.values()}
            if len(chunks) != 1:
                raise ValueError(
                    f"all pools must share the chunk (token) dim, got "
                    f"{self.kv_pools}")
            self.kv_chunk = int(next(iter(chunks)))
            lanes = lease_ops.LANES
            self._pool_meta: Dict[str, Dict[str, int]] = {}
            off = 0
            for name, shape in self.kv_pools.items():
                te = int(np.prod(shape[1:]))
                row = -(-te // lanes) * lanes
                self._pool_meta[name] = {"offset": off, "token_elems": te,
                                         "token_row": row}
                off += row
            self.kv_token_row = off
            # stored as token rows, the layout every kernel reads: a
            # reshape to (n_blocks, chunk * row) would be a relayout copy
            # of the whole pool on the chip
            rows = (self.n_blocks * self.kv_chunk, self.kv_token_row)
            if backend == "pallas":
                self._kv_pool = jnp.zeros(rows, kv_dtype)
            else:
                self._kv_pool = np.zeros(rows, np.dtype(kv_dtype))
            self._kv_valid = np.zeros(self.n_blocks, bool)
            self.stats.kv_pool_tokens = {n: 0 for n in self.kv_pools}
        # runtime lease sanitizer (repro.analysis.sanitize): host-side
        # invariant checks after every transition.  Off by default; one
        # ``is None`` branch per op when disabled.
        if sanitize is None:
            sanitize = os.environ.get("TARDIS_SANITIZE", "0").lower() \
                not in ("", "0", "false", "off")
        self._san = None
        if sanitize:
            from ..analysis.sanitize import LeaseSanitizer
            self._san = LeaseSanitizer(self)

    @property
    def sanitize_checks(self) -> int:
        """Transitions checked by the sanitizer (0 when it is off)."""
        return self._san.checks if self._san is not None else 0

    @property
    def lease_max(self) -> int:
        """Hard upper bound on any lease this engine may grant (== the
        static lease when the predictor is off) -- the sanitizer's cap."""
        return self.policy.lease_max

    @property
    def pred_lease(self) -> np.ndarray:
        """Per-block predicted leases (pinned at ``lease`` when the
        predictor is off).  Values are timestamp DELTAS: rebases never
        touch them."""
        return self._pred_lease

    def set_pred_lease(self, idx, values) -> None:
        """Install predictor state for blocks (page migration / baseline
        sync: the prediction travels with the block)."""
        idx = np.atleast_1d(np.asarray(idx, np.int64))
        vals = np.broadcast_to(np.asarray(values, np.int32), idx.shape)
        self._pred_lease[idx] = np.clip(vals, self.policy.lease_min,
                                        self.policy.lease_max)

    def _lease_arg(self):
        """The lease operand for a lease pass: the per-block predicted
        vector under the predictor, else the static scalar (the kernels
        broadcast either)."""
        if self.policy.predictor:
            return self._pred_lease
        return np.int32(self.lease)

    def set_tables(self, wts, rts) -> None:
        """Verification seam: load externally computed ``(wts, rts)`` tables.

        Used by the analysis bridge (:mod:`repro.analysis.bridge`) to replay
        model-enumerated transitions through this engine, and by tests that
        need a specific table state.  Resets the sanitizer's monotonicity
        baseline -- the loaded state is a new ground truth, not a
        transition.
        """
        wts = np.asarray(wts, np.int32).reshape(self.n_blocks)
        rts = np.asarray(rts, np.int32).reshape(self.n_blocks)
        if (wts > rts).any():
            raise ValueError("set_tables: wts > rts")
        if self.backend == "pallas":
            self._wts = jnp.asarray(wts)
            self._rts = jnp.asarray(rts)
        else:
            self._wts = wts.copy()
            self._rts = rts.copy()
        if self._san is not None:
            self._san.rebaseline(self)

    # -- table views --------------------------------------------------------

    @property
    def wts(self) -> np.ndarray:
        return np.asarray(self._wts)

    @property
    def rts(self) -> np.ndarray:
        return np.asarray(self._rts)

    # -- paged KV pool ------------------------------------------------------

    @property
    def has_kv(self) -> bool:
        return self.kv_pools is not None

    @property
    def pool_names(self) -> List[str]:
        return list(self.kv_pools) if self.kv_pools else []

    def pool_offset(self, pool: str) -> int:
        """Static column offset of a named stack's segment inside the
        interleaved token row (a LANES multiple -- the decode kernels use
        the same layout)."""
        return self._pool_meta[pool]["offset"]

    def pool_token_row(self, pool: str) -> int:
        return self._pool_meta[pool]["token_row"]

    def pool_token_elems(self, pool: str) -> int:
        return self._pool_meta[pool]["token_elems"]

    def _single_pool(self) -> str:
        if len(self.kv_pools) != 1:
            raise ValueError(
                f"engine has pools {self.pool_names}: name one explicitly")
        return next(iter(self.kv_pools))

    def kv_ok(self, bid: int) -> bool:
        """True when the pool slot holds content for the block's current
        tag (set by write_kv, cleared by invalidate_kv).  Per block: every
        stack's segment is published together or not at all."""
        return bool(self.has_kv and self._kv_valid[bid])

    def kv_valid_count(self) -> int:
        return int(self._kv_valid.sum()) if self.has_kv else 0

    def _pack_rows(self, blocks, n: int, xp, pool: str):
        """(n, *pool_shape) payloads -> (n, chunk, row_p) token-padded."""
        meta = self._pool_meta[pool]
        pad = ((0, 0), (0, 0),
               (0, meta["token_row"] - meta["token_elems"]))
        return xp.pad(xp.asarray(blocks).reshape(
            n, self.kv_chunk, meta["token_elems"]), pad)

    def write_kv(self, idx, blocks) -> None:
        """Scatter block payloads into the pool(s) in ONE dispatch.

        ``blocks`` is (n, *kv_block_shape) for a single-pool engine, or a
        mapping ``{pool_name: (n, *pool_shape)}`` naming EVERY pool -- a
        block's content is published for all stacks at once (the validity
        bit is per block), which is what makes a block id lease both
        stacks' payloads in one transition.
        """
        idx = np.atleast_1d(np.asarray(idx, np.int64))
        if not idx.size:
            return
        if not isinstance(blocks, Mapping):
            blocks = {self._single_pool(): blocks}
        if set(blocks) != set(self.kv_pools):
            raise ValueError(f"write_kv needs every pool "
                             f"{self.pool_names}, got {sorted(blocks)}")
        xp = jnp if self.backend == "pallas" else np
        flat = xp.concatenate(
            [self._pack_rows(blocks[name], idx.size, xp, name)
             for name in self.kv_pools], axis=-1
        ).reshape(idx.size * self.kv_chunk, self.kv_token_row)
        rows_idx = self._token_rows(idx)
        if self.backend == "pallas":
            with warnings.catch_warnings():
                # CPU XLA can't honor the donation; the TPU path does
                warnings.filterwarnings("ignore", message=".*donated.*")
                self._kv_pool = _scatter_rows(self._kv_pool,
                                              jnp.asarray(rows_idx), flat)
        else:
            self._kv_pool[rows_idx] = flat.astype(self._kv_pool.dtype)
        self._kv_valid[idx] = True
        self.stats.kv_blocks_written += int(idx.size)
        if self._san is not None:
            self._san.after(self, "write_kv", blocks=idx)

    def _token_rows(self, idx: np.ndarray) -> np.ndarray:
        """Pool token-row ids of blocks ``idx``, ``chunk`` per block."""
        return (idx[:, None] * self.kv_chunk
                + np.arange(self.kv_chunk)).reshape(-1)

    def _rows_to_blocks(self, rows, n: int, pool: str):
        """(n, chunk, row_p) padded rows -> (n, *pool_shape) payloads."""
        meta = self._pool_meta[pool]
        return rows[:, :, :meta["token_elems"]].reshape(
            (n,) + self.kv_pools[pool])

    def read_kv(self, idx, pool: Optional[str] = None):
        """Materialize pool payloads for leased block ids via the Pallas
        gather kernel.

        Single-pool engines return (n, *kv_block_shape).  Multi-pool
        engines return ``{pool_name: (n, *pool_shape)}`` from ONE
        full-row gather; ``pool=name`` instead gathers just that stack's
        column window (the kernel's pool-offset index-map dimension) and
        returns its array.
        """
        idx = np.atleast_1d(np.asarray(idx, np.int64))
        dtype = np.asarray(self._kv_pool[:0]).dtype
        if not idx.size:
            if pool is not None or self.kv_block_shape:
                shape = self.kv_pools[pool] if pool else self.kv_block_shape
                return np.zeros((0,) + shape, dtype)
            return {n_: np.zeros((0,) + s, dtype)
                    for n_, s in self.kv_pools.items()}
        # token-granular gather over the whole token row, or over one
        # stack's column window
        lo, width = 0, self.kv_token_row
        if pool is not None:
            meta = self._pool_meta[pool]
            lo, width = meta["offset"], meta["token_row"]
        rows_idx = self._token_rows(idx)
        if self.backend == "pallas":
            rows = lease_ops.gather_blocks(
                self._kv_pool, jnp.asarray(rows_idx, jnp.int32),
                col_lo=lo, width=width, interpret=self.interpret)
        else:
            rows = self._kv_pool[rows_idx, lo:lo + width]
        self.stats.kv_blocks_read += int(idx.size)
        rows = rows.reshape(idx.size, self.kv_chunk, width)
        if pool is not None:
            return self._rows_to_blocks(rows, idx.size, pool)
        out = {}
        for name, meta in self._pool_meta.items():
            seg = rows[:, :, meta["offset"]:meta["offset"]
                       + meta["token_row"]]
            out[name] = self._rows_to_blocks(seg, idx.size, name)
        if self.kv_block_shape:
            return out[self._single_pool()]
        return out

    def invalidate_kv(self, idx) -> None:
        """Free pool slots on collision eviction (re-tag): the content no
        longer matches the slot's tag.  Zero messages -- readers holding
        leases on the old content keep their private copies."""
        idx = np.atleast_1d(np.asarray(idx, np.int64))
        freed = int(self._kv_valid[idx].sum())
        self._kv_valid[idx] = False
        self.stats.kv_evictions += freed
        if self._san is not None:
            self._san.after(self, "invalidate_kv", blocks=idx)

    # -- decode pages: allocator + token-granular append --------------------

    def free_page_count(self) -> int:
        """Pages left in the allocatable region (admission control bound)."""
        return len(self._free_pages)

    def alloc_pages(self, n: int) -> np.ndarray:
        """Pop ``n`` pages off the free list (lowest ids first).  Callers
        gate admission on :meth:`free_page_count`; running dry here is a
        scheduling bug, not back-pressure."""
        if n > len(self._free_pages):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free_pages)}")
        ids = np.asarray([self._free_pages.pop() for _ in range(n)],
                         np.int64)
        self._free_set.difference_update(int(b) for b in ids)
        self.stats.pages_allocated += int(n)
        if self._san is not None:
            self._san.after(self, "alloc_pages", idx=ids)
        return ids

    def free_pages(self, idx) -> None:
        """Return pages to the free list the moment a request finishes;
        their payload slots are invalidated (no messages, like eviction).

        Freeing a page that is already free, was never handed out by
        :meth:`alloc_pages` (the whole allocatable region starts free, so
        any in-region page that is not free IS outstanding), or lies
        outside the allocatable region raises ``ValueError`` before any
        state changes -- a silent accept would put the id on the free list
        twice and hand the same page to two requests.
        """
        idx = np.atleast_1d(np.asarray(idx, np.int64))
        if not idx.size:
            return
        ids = [int(b) for b in idx]
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"free_pages: duplicate page ids in one call: {sorted(ids)}")
        for b in ids:
            if not self.alloc_reserve <= b < self.n_blocks:
                raise ValueError(
                    f"free_pages: page {b} outside the allocatable region "
                    f"[{self.alloc_reserve}, {self.n_blocks})")
            if b in self._free_set:
                raise ValueError(
                    f"free_pages: page {b} is already free (double free, "
                    f"or never allocated) -- freeing again would hand it "
                    f"to two requests")
        if self.has_kv:
            self._kv_valid[idx] = False
        for b in sorted(ids, reverse=True):
            self._free_pages.append(b)
            self._free_set.add(b)
        self.stats.pages_freed += int(idx.size)
        if self._san is not None:
            self._san.after(self, "free_pages", blocks=idx)

    def kv_rows_view(self):
        """The pool as (n_blocks*chunk, token_row) device rows -- the
        substrate the paged decode step reads and appends against."""
        if self.backend == "pallas":
            return self._kv_pool
        return jnp.asarray(self._kv_pool)

    def set_kv_rows(self, rows, tokens_appended: int = 0) -> None:
        """Write back the (possibly donated) rows view after a jitted
        decode step appended token KV in place.  An appended row spans the
        whole interleaved token row, so it feeds every stack's counter."""
        if self.backend == "pallas":
            self._kv_pool = rows
        else:
            self._kv_pool = np.array(rows)        # writable: appends land here
        self.stats.kv_tokens_appended += int(tokens_appended)
        for name in self.kv_pools:
            self.stats.kv_pool_tokens[name] = (
                self.stats.kv_pool_tokens.get(name, 0)
                + int(tokens_appended))
        if self._san is not None:
            self._san.after(self, "set_kv_rows")

    def append_kv(self, rows_idx, token_rows,
                  pool: Optional[str] = None) -> None:
        """Host-side token append: scatter token rows into flat token slots
        ``rows_idx`` (= block_id * chunk + slot) through the ``tardis_lease``
        scatter kernel.

        ``pool=None`` appends FULL token rows: (n, kv_token_row) already in
        the interleaved multi-stack layout (the serving path packs every
        stack's segment -- one scatter covers both cache stacks), or, on a
        single-pool engine, the legacy unpadded (n, token_elems) form.
        Marks the touched blocks' slots as holding content (prefill writing
        a request's own pages).

        ``pool=name`` appends one stack's (n, pool_token_elems) rows into
        its column window only -- neighbors' segments keep their bits, and
        validity is left untouched (publishing a block's content for every
        stack is ``write_kv``'s job).

        A negative id writes nothing (a prefill bucket's pad rows past its
        prompt).  On the ``pallas`` backend device rows are scattered as
        they come, without a host copy; the numpy mirror converts them.
        """
        rows_idx = np.atleast_1d(np.asarray(rows_idx, np.int64))
        keep = rows_idx >= 0
        n_kept = int(keep.sum())
        if not n_kept:
            return
        if pool is not None:
            meta = self._pool_meta[pool]
            if self.backend == "pallas":
                with warnings.catch_warnings():
                    warnings.filterwarnings("ignore", message=".*donat.*")
                    self._kv_pool = lease_ops.append_rows(
                        self._kv_pool, jnp.asarray(rows_idx, jnp.int32),
                        jnp.asarray(token_rows).reshape(
                            rows_idx.size, meta["token_elems"]),
                        col_lo=meta["offset"], width=meta["token_row"],
                        interpret=self.interpret)
            else:
                # write the stack's WHOLE lane-padded window (zeros in the
                # padding), exactly like the kernel's LANES-block DMA --
                # touching only token_elems columns would leave the padding
                # bits behind and break kernel/mirror bit-identity
                rows = np.asarray(token_rows).reshape(
                    rows_idx.size, meta["token_elems"])[keep]
                flat = np.zeros((n_kept, meta["token_row"]),
                                self._kv_pool.dtype)
                flat[:, :meta["token_elems"]] = rows.astype(
                    self._kv_pool.dtype)
                self._kv_pool[
                    rows_idx[keep],
                    meta["offset"]:meta["offset"] + meta["token_row"]] = flat
            self.stats.kv_tokens_appended += n_kept
            self.stats.kv_pool_tokens[pool] = (
                self.stats.kv_pool_tokens.get(pool, 0) + n_kept)
            if self._san is not None:       # validity untouched on this path
                self._san.after(self, "append_kv",
                                blocks=np.zeros(0, np.int64))
            return
        xp = jnp if self.backend == "pallas" else np
        rows = xp.asarray(token_rows).reshape(rows_idx.size, -1)
        if rows.shape[1] != self.kv_token_row:
            # legacy single-pool form: unpadded token_elems rows
            meta = self._pool_meta[self._single_pool()]
            rows = rows.reshape(rows_idx.size, meta["token_elems"])
        if self.backend == "pallas":
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=".*donat.*")
                self._kv_pool = lease_ops.append_rows(
                    self._kv_pool, jnp.asarray(rows_idx, jnp.int32), rows,
                    interpret=self.interpret)
        else:
            flat = np.zeros((n_kept, self.kv_token_row), self._kv_pool.dtype)
            flat[:, :rows.shape[1]] = rows[keep]
            self._kv_pool[rows_idx[keep]] = flat
        blocks = np.unique(rows_idx[keep] // self.kv_chunk)
        self._kv_valid[blocks] = True
        self.stats.kv_tokens_appended += n_kept
        for name in self.kv_pools:       # a full row feeds every stack
            self.stats.kv_pool_tokens[name] = (
                self.stats.kv_pool_tokens.get(name, 0) + n_kept)
        if self._san is not None:
            self._san.after(self, "append_kv", blocks=blocks)

    # -- protocol transitions ----------------------------------------------

    def read(self, idx, pts: int, req_wts=None) -> ReadResult:
        """Serve loads/renewals for the blocks in ``idx`` at reader ``pts``.

        Every selected block's lease extends to ``max(rts, wts + lease,
        pts + lease)`` (Table III SH_REQ); the reader's program timestamp
        advances over the consumed versions (Table I load).  ``req_wts``
        (aligned with ``idx``) is the requester's cached version per block;
        matches are answered data-less (RENEW_REP).  None or -1 entries mean
        "no cached copy" and always transfer a payload.
        """
        idx = np.atleast_1d(np.asarray(idx, np.int64))
        if idx.size == 0:
            return ReadResult(np.zeros(0, bool), np.zeros(0, bool),
                              np.zeros(0, np.int32), np.zeros(0, np.int32),
                              int(pts))
        mask = np.zeros(self.n_blocks, np.int32)
        mask[idx] = 1
        req = np.full(self.n_blocks, -1, np.int32)
        if req_wts is not None:
            req[idx] = np.asarray([-1 if r is None else r
                                   for r in np.ravel(req_wts)], np.int32)

        if self.backend == "pallas":
            out = lease_ops.masked_lease_check(
                self._wts, self._rts, jnp.asarray(req), jnp.asarray(mask),
                np.int32(pts), self._lease_arg(),
                interpret=self.interpret)
            self._rts = out["new_rts"]
            expired, renew_ok, wts_at, rts_at = (np.asarray(x) for x in
                _gather4(out["expired"], out["renew_ok"], self._wts,
                         self._rts, jnp.asarray(idx)))
            new_pts = int(out["new_pts"])
        else:
            m = mask.astype(bool)
            lv = self._lease_arg()
            expired_f = m & (pts > self._rts)
            renew_f = m & (req == self._wts)
            ext = np.maximum(np.maximum(self._rts, self._wts + lv),
                             np.int32(pts) + lv)
            consumed = np.where(m & (pts <= self._rts), self._wts, 0)
            self._rts = np.where(m, ext, self._rts).astype(np.int32)
            expired = expired_f[idx]
            renew_ok = renew_f[idx]
            wts_at = self._wts[idx]
            rts_at = self._rts[idx]
            new_pts = int(max(pts, consumed.max(initial=0)))

        n = int(idx.size)
        had_copy = (req[idx] >= 0)
        data_less = int(np.sum(renew_ok & had_copy))
        payload = n - data_less
        st = self.stats
        st.read_ops += 1
        st.reads += n
        st.expired += int(np.sum(expired))
        st.renewals += int(np.sum(had_copy))
        st.data_less += data_less
        st.payload_transfers += payload
        st.payload_bytes += payload * self.block_bytes
        st.flits += n * protocol.MESSAGE_FLITS["SH_REQ"]
        st.flits += data_less * protocol.MESSAGE_FLITS["RENEW_REP"]
        # SH_REP: header + timestamp flits, plus the block payload.
        st.flits += payload * (protocol.MESSAGE_FLITS["RENEW_REP"]
                               + protocol.data_flits(self.block_bytes))
        if self.policy.predictor:
            # a data-less renewal from a holder of a cached copy means that
            # requester's lease aged out before the version changed: wasted
            # traffic, grow the block's next lease.  Requesters only renew
            # on local expiry, so no owner-side expiry gate -- with several
            # readers the owner rts is often already extended past the
            # requester's pts by a peer's renewal, yet the message was
            # still sent
            grow = renew_ok & had_copy
            if np.any(grow):
                b = idx[grow]
                self._pred_lease[b] = np.minimum(
                    self.policy.lease_max, self._pred_lease[b] * 2)
                st.pred_grows += int(np.sum(grow))
        if self._san is not None:
            self._san.after(self, "read", pts=int(pts), new_pts=new_pts)
        return ReadResult(expired, renew_ok, wts_at, rts_at, new_pts)

    def read_many(self, groups: Sequence, pts,
                  req_wts: Optional[Union[Dict[int, int], Sequence]] = None
                  ) -> ReadManyResult:
        """Per-wave batched read: G requester groups, ONE kernel dispatch.

        ``groups`` is a list of per-requester block-id sequences (they may
        overlap -- a wave sharing a system prompt names the same blocks G
        times and still costs a single masked-lease pass).  ``pts`` is a
        scalar (the wave's shared program timestamp, the serving case) or a
        (G,) vector.  ``req_wts`` maps block id -> the requesters' cached
        version (a dict, or an array aligned with the sorted union); the
        wave shares one requester-side cache, so it is per-block.

        With a shared ``pts``, the table state and ``max(new_pts)`` are
        bit-identical to issuing the G reads sequentially at that pts (the
        per-group Table III extensions commute); per-group flags are
        evaluated against the pre-call snapshot.
        """
        groups = [np.atleast_1d(np.asarray(g, np.int64)) for g in groups]
        n_groups = len(groups)
        pts_vec = np.broadcast_to(np.asarray(pts, np.int32),
                                  (n_groups,)).copy()
        union = sorted({int(b) for g in groups for b in g})
        if not union:
            return ReadManyResult(
                np.zeros(0, np.int64), np.zeros((n_groups, 0), bool),
                np.zeros((n_groups, 0), bool), np.zeros(0, np.int32),
                np.zeros(0, np.int32), pts_vec)
        union_idx = np.asarray(union, np.int64)
        # the serving hot case is a wave of identical requesters (shared
        # system prompt): collapse duplicate (blocks, pts) rows so the
        # kernel runs one mask row per DISTINCT requester, and per-group
        # results fan back out (also keeps the traced G small and stable).
        row_of, ukeys = [], {}
        for g, idx in enumerate(groups):
            key = (tuple(sorted({int(b) for b in idx})), int(pts_vec[g]))
            row_of.append(ukeys.setdefault(key, len(ukeys)))
        row_of = np.asarray(row_of)
        n_rows = len(ukeys)
        pts_rows = np.asarray([k[1] for k in ukeys], np.int32)
        masks = np.zeros((n_rows, self.n_blocks), np.int32)
        for key, row in ukeys.items():
            masks[row, list(key[0])] = 1
        req = np.full(self.n_blocks, -1, np.int32)
        if req_wts is not None:
            if isinstance(req_wts, dict):
                for bid, w in req_wts.items():
                    req[bid] = -1 if w is None else int(w)
            else:
                req[union_idx] = np.asarray(
                    [-1 if r is None else r for r in np.ravel(req_wts)],
                    np.int32)

        if self.backend == "pallas":
            out = lease_ops.masked_lease_check_many(
                self._wts, self._rts, jnp.asarray(req), jnp.asarray(masks),
                jnp.asarray(pts_rows), self._lease_arg(),
                interpret=self.interpret)
            self._rts = out["new_rts"]
            expired, renew_ok, wts_at, rts_at = (np.asarray(x) for x in
                _gather_many(out["expired"], out["renew_ok"], self._wts,
                             self._rts, jnp.asarray(union_idx)))
            new_pts = np.asarray(out["new_pts"])
        else:
            m = masks.astype(bool)
            lv = self._lease_arg()
            rts0 = self._rts
            expired_f = m & (pts_rows[:, None] > rts0[None, :])
            renew_f = m & (req[None, :] == self._wts[None, :])
            new_rts = rts0
            new_pts = pts_rows.copy()
            for g in range(n_rows):
                ext = np.maximum(
                    np.maximum(rts0, self._wts + lv),
                    np.int32(pts_rows[g]) + lv)
                new_rts = np.where(m[g], np.maximum(new_rts, ext), new_rts)
                consumed = np.where(m[g] & (pts_rows[g] <= rts0),
                                    self._wts, 0)
                new_pts[g] = max(int(pts_rows[g]),
                                 int(consumed.max(initial=0)))
            self._rts = new_rts.astype(np.int32)
            expired = expired_f[:, union_idx]
            renew_ok = renew_f[:, union_idx]
            wts_at = self._wts[union_idx]
            rts_at = self._rts[union_idx]
        expired = expired[row_of]              # fan the distinct-row results
        renew_ok = renew_ok[row_of]            # back out to the G groups
        new_pts = new_pts[row_of]

        n = int(union_idx.size)
        had_copy = (req[union_idx] >= 0)
        renew_u = renew_ok.any(axis=0)
        data_less = int(np.sum(renew_u & had_copy))
        payload = n - data_less
        st = self.stats
        st.read_ops += 1             # the whole wave: one dispatch
        st.reads += n
        st.expired += int(np.sum(expired.any(axis=0)))
        st.renewals += int(np.sum(had_copy))
        st.data_less += data_less
        st.payload_transfers += payload
        st.payload_bytes += payload * self.block_bytes
        st.flits += n * protocol.MESSAGE_FLITS["SH_REQ"]
        st.flits += data_less * protocol.MESSAGE_FLITS["RENEW_REP"]
        st.flits += payload * (protocol.MESSAGE_FLITS["RENEW_REP"]
                               + protocol.data_flits(self.block_bytes))
        if self.policy.predictor:
            # same rule as read(): every data-less renewal of a held copy
            # is waste, however many groups named the block this wave
            grow = renew_u & had_copy
            if np.any(grow):
                b = union_idx[grow]
                self._pred_lease[b] = np.minimum(
                    self.policy.lease_max, self._pred_lease[b] * 2)
                st.pred_grows += int(np.sum(grow))
        if self._san is not None:
            self._san.after(self, "read_many", pts=pts_vec,
                            new_pts=new_pts)
        return ReadManyResult(union_idx, expired, renew_ok, wts_at, rts_at,
                              new_pts)

    def write_many(self, groups: Sequence, pts: int) -> int:
        """Per-wave batched write: the union of the groups' blocks gets ONE
        jump-ahead (one logical tick for the whole wave), replacing G
        full-table dispatch pairs.  Returns the wave's new pts."""
        union = sorted({int(b) for g in groups
                        for b in np.atleast_1d(np.asarray(g, np.int64))})
        if not union:
            return int(pts)
        return self.write(np.asarray(union, np.int64), pts)

    def write(self, idx, pts: int) -> int:
        """Writer jump-ahead over every block in ``idx`` (Table I store).

        The new version's timestamp clears every outstanding read lease:
        ``ts = max(pts, max(rts[idx]) + 1)``; each block gets wts = rts = ts.
        No invalidation is sent to anybody.  Returns the writer's new pts.
        """
        idx = np.atleast_1d(np.asarray(idx, np.int64))
        if idx.size == 0:
            return int(pts)
        mask = np.zeros(self.n_blocks, np.int32)
        mask[idx] = 1

        if self.backend == "pallas":
            self._wts, self._rts, ts = lease_ops.write_advance(
                self._wts, self._rts, jnp.asarray(mask), np.int32(pts),
                interpret=self.interpret)
            ts = int(ts)
        else:
            m = mask.astype(bool)
            top = int(np.where(m, self._rts, -1).max(initial=-1))
            ts = max(int(pts), top + 1)
            self._wts = np.where(m, np.int32(ts), self._wts).astype(np.int32)
            self._rts = np.where(m, np.int32(ts), self._rts).astype(np.int32)

        n = int(idx.size)
        st = self.stats
        st.write_ops += 1
        st.writes += n
        st.payload_bytes += n * self.block_bytes
        # publish: one header flit + payload per block (DRAM_ST_REQ shape).
        st.flits += n * (1 + protocol.data_flits(self.block_bytes))
        if self.policy.predictor:
            # a write had to clear the lease: shrink so the next lease
            # blocks writers for less long (livelock-free -- the write
            # already jumped ahead regardless of the prediction)
            self._pred_lease[idx] = np.maximum(
                self.policy.lease_min, self._pred_lease[idx] // 2)
            st.pred_shrinks += n
        if self._san is not None:
            self._san.after(self, "write", idx=idx, pts=int(pts), ts=ts)
        return ts

    # -- wraparound guard ---------------------------------------------------

    def maybe_rebase(self) -> int:
        """Shift the whole table down when timestamps approach 2**ts_bits.

        Returns the shift applied (0 when none was needed).  Every caller
        holding a program timestamp or cached ``(wts, rts)`` leases must
        subtract the same shift; a cached lease whose rts falls below the
        new base must be dropped (a private Shared line cannot raise its
        rts unilaterally -- see ``timestamps.apply_rebase``).
        """
        if self.backend == "pallas":
            max_ts = int(jnp.max(self._rts)) if self.n_blocks else 0
        else:
            max_ts = int(np.max(self._rts, initial=0))
        if not timestamps.rebase_needed(max_ts, 0, self.ts_bits):
            return 0
        return self.force_rebase(timestamps.rebase_amount(self.ts_bits))

    def force_rebase(self, shift: int) -> int:
        """Apply a given downward shift unconditionally.

        The sharded directory uses this to keep every shard on ONE
        timestamp base: when any shard trips its guard, the coordinator
        applies the same shift to all shards so cross-shard timestamp
        order survives the rebase.  Returns the shift.
        """
        shift = int(shift)
        if shift <= 0:
            return 0
        if self.backend == "pallas":
            self._wts = jnp.maximum(self._wts - shift, 0)
            self._rts = jnp.maximum(self._rts - shift, 0)
        else:
            self._wts = np.maximum(self._wts - shift, 0).astype(np.int32)
            self._rts = np.maximum(self._rts - shift, 0).astype(np.int32)
        self.ts_shift += shift
        self.stats.rebases += 1
        if self._san is not None:
            self._san.after(self, "rebase")
        return shift

    @staticmethod
    def rebase_pts(pts: int, shift: int) -> int:
        """A caller's program timestamp after an engine rebase."""
        return max(0, int(pts) - int(shift))

    # -- reporting ----------------------------------------------------------

    def report(self) -> dict:
        st = self.stats
        per_pool = {}
        if self.has_kv:
            for name in self.kv_pools:
                per_pool[f"kv_pool_tokens_{name}"] = \
                    st.kv_pool_tokens.get(name, 0)
        return {
            **per_pool,
            "blocks_read": st.reads,
            "blocks_written": st.writes,
            "read_ops": st.read_ops,
            "write_ops": st.write_ops,
            "kv_blocks_written": st.kv_blocks_written,
            "kv_blocks_read": st.kv_blocks_read,
            "kv_evictions": st.kv_evictions,
            "kv_tokens_appended": st.kv_tokens_appended,
            "pages_allocated": st.pages_allocated,
            "pages_freed": st.pages_freed,
            "free_pages": self.free_page_count(),
            "expired_leases": st.expired,
            "renewals": st.renewals,
            "data_less_renewals": st.data_less,
            "payload_transfers": st.payload_transfers,
            "payload_bytes": st.payload_bytes,
            "wire_flits": st.flits,
            "wire_bytes": st.wire_bytes,
            "rebases": st.rebases,
            "pred_grows": st.pred_grows,
            "pred_shrinks": st.pred_shrinks,
            "pred_lease_lo": int(self._pred_lease.min(initial=self.lease)),
            "pred_lease_hi": int(self._pred_lease.max(initial=self.lease)),
            "sanitize_checks": self.sanitize_checks,
        }
