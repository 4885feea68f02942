"""CoherencePolicy: the one-object coherence configuration (Tardis 2.0).

Covers the policy dataclass itself (validation, predictor bounds, the
grow/shrink step rules every engine shares), the serving-cluster
deprecation shim for the legacy ``kv_lease=``/``ts_bits=`` kwargs, and
the adaptive-lease state machine end to end: predictions survive ``ts_bits`` rebases unshifted
(they are timestamp *deltas*), travel with pages across shard-directory
migration, evolve bit-identically to a single-engine oracle under sharded
waves, and match between the Pallas kernels and the numpy mirror.
"""
import warnings

import numpy as np
import pytest

from repro.core import (CoherencePolicy, CONSISTENCY_MODELS, LeaseEngine,
                        ShardedLeaseDirectory)
from repro.core.policy import resolve_policy

POOLS = {"k": (1, 2), "v": (1, 2)}


def _page(val):
    return {n: np.full((1,) + s, val, np.float32) for n, s in POOLS.items()}


# ---------------------------------------------------------------------------
# The dataclass: defaults, bounds, step rules, validation
# ---------------------------------------------------------------------------

def test_policy_static_bounds_collapse_to_lease():
    p = CoherencePolicy(lease=16)
    assert (p.lease_min, p.lease_max) == (16, 16)
    assert not p.predictor and p.consistency == "sc"
    assert not p.skip_expired_renewal()
    # the degenerate predictor: grow/shrink are identities at tight bounds
    assert p.grow(16) == 16 and p.shrink(16) == 16


def test_policy_predictor_default_and_explicit_bounds():
    p = CoherencePolicy(lease=16, predictor=True)
    assert (p.lease_min, p.lease_max) == (4, 128)       # [lease//4, lease*8]
    q = CoherencePolicy(lease=16, predictor=True, lease_min=2, lease_max=32)
    assert (q.lease_min, q.lease_max) == (2, 32)
    assert q.grow(32) == 32 and q.grow(20) == 32        # clamped doubling
    assert q.shrink(2) == 2 and q.shrink(5) == 2        # clamped halving
    r = q.with_(consistency="tso")
    assert r.skip_expired_renewal() and q.consistency == "sc"
    assert CoherencePolicy.from_legacy(lease=8, ts_bits=12).ts_bits == 12


def test_policy_validation_rejects_bad_configs():
    with pytest.raises(ValueError, match="consistency"):
        CoherencePolicy(consistency="weak")
    with pytest.raises(ValueError, match="lease must be"):
        CoherencePolicy(lease=0)
    with pytest.raises(ValueError, match="lease_min <= lease"):
        CoherencePolicy(lease=4, lease_min=8, predictor=True)
    with pytest.raises(ValueError, match="lease_min <= lease"):
        CoherencePolicy(lease=4, lease_max=2, predictor=True)
    with pytest.raises(ValueError, match="ts_bits"):
        CoherencePolicy(ts_bits=1)
    assert set(CONSISTENCY_MODELS) == {"sc", "tso", "rc"}


def test_resolve_policy_precedence():
    given = CoherencePolicy(lease=5)
    assert resolve_policy(given, lease=99, ts_bits=4) is given
    folded = resolve_policy(None, lease=7, ts_bits=9)
    assert (folded.lease, folded.ts_bits) == (7, 9)
    defaulted = resolve_policy(None, lease=None, ts_bits=None,
                               default_lease=21, default_ts_bits=11)
    assert (defaulted.lease, defaulted.ts_bits) == (21, 11)


# ---------------------------------------------------------------------------
# Serving-cluster API: policy= is first class, legacy kwargs one release out
# ---------------------------------------------------------------------------

def _tiny_cluster(**kw):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch, reduced
    from repro.models import init_params
    from repro.runtime import ServingCluster

    cfg = reduced(get_arch("tinyllama-1.1b"), n_layers=2, d_model=64,
                  vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return ServingCluster(cfg, lambda: params, **kw)


def test_legacy_kv_lease_kwarg_deprecated_but_working():
    with pytest.warns(DeprecationWarning, match="kv_lease=/ts_bits="):
        cluster = _tiny_cluster(n_replicas=1, prefix_block_tokens=4,
                                kv_lease=32)
    assert cluster.policy.lease == 32
    assert cluster.prefix_engine.lease == 32
    with pytest.warns(DeprecationWarning):
        cluster = _tiny_cluster(n_replicas=1, prefix_block_tokens=4,
                                ts_bits=12)
    assert cluster.policy.ts_bits == 12


def test_policy_kwarg_is_silent_and_exclusive():
    pol = CoherencePolicy(consistency="tso", lease=32, predictor=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cluster = _tiny_cluster(n_replicas=1, prefix_block_tokens=4,
                                policy=pol)
    assert cluster.policy is pol
    assert cluster.prefix_engine.policy is pol
    with pytest.raises(ValueError, match="not both"):
        _tiny_cluster(n_replicas=1, prefix_block_tokens=4,
                      policy=pol, kv_lease=16)


# ---------------------------------------------------------------------------
# Predictor state machine on one engine
# ---------------------------------------------------------------------------

def _pol(**kw):
    kw.setdefault("lease", 4)
    kw.setdefault("predictor", True)
    kw.setdefault("lease_min", 1)
    kw.setdefault("lease_max", 64)
    return CoherencePolicy(**kw)


def test_predictor_grows_on_wasted_renewal_shrinks_on_write():
    eng = LeaseEngine(4, policy=_pol(), backend="numpy")
    pts = eng.write([0], 0)                            # every write shrinks
    assert int(eng.pred_lease[0]) == _pol().shrink(4) == 2
    r = eng.read([0], pts, req_wts=[-1])               # fetch: no copy yet
    assert int(eng.pred_lease[0]) == 2                 # fetch never grows
    wts = int(r.wts[0])
    expect = 2
    for _ in range(3):                                 # wasted renewals:
        pts = int(r.rts[0]) + 1                        # expired ...
        r = eng.read([0], pts, req_wts=[wts])          # ... and unchanged
        expect = _pol().grow(expect)                   # 2 -> 4 -> 8 -> 16
        assert int(eng.pred_lease[0]) == expect
    assert eng.stats.pred_grows == 3
    pts = eng.write([0], int(r.new_pts))               # writer was blocked
    assert int(eng.pred_lease[0]) == _pol().shrink(expect)
    assert eng.stats.pred_shrinks == 2                 # seed write + this one
    # stale-version renewal (copy outdated): payload refresh, no growth
    r = eng.read([0], pts, req_wts=[wts])
    assert not bool(r.renew_ok[0])
    assert int(eng.pred_lease[0]) == _pol().shrink(expect)
    rep = eng.report()
    assert rep["pred_grows"] == 3 and rep["pred_shrinks"] == 2
    assert rep["pred_lease_lo"] <= rep["pred_lease_hi"]


def test_predictor_off_is_bit_identical_to_static():
    """A predictor-off policy is the legacy protocol exactly: same tables
    as a legacy-kwarg engine on the same stream, zero predictor motion."""
    a = LeaseEngine(4, lease=4, backend="numpy")
    b = LeaseEngine(4, policy=CoherencePolicy(lease=4), backend="numpy")
    pa = pb = 0
    for step in range(12):
        if step % 3 == 0:
            pa = a.write([step % 4], pa)
            pb = b.write([step % 4], pb)
        else:
            ra = a.read([step % 4], pa, req_wts=[-1])
            rb = b.read([step % 4], pb, req_wts=[-1])
            pa, pb = int(ra.new_pts), int(rb.new_pts)
    np.testing.assert_array_equal(a.wts, b.wts)
    np.testing.assert_array_equal(a.rts, b.rts)
    assert b.stats.pred_grows == 0 and b.stats.pred_shrinks == 0


def test_predictor_survives_ts_bits_rebase():
    """Predicted leases are timestamp DELTAS: a table rebase shifts wts/rts
    down uniformly but must leave every per-block prediction untouched."""
    eng = LeaseEngine(4, policy=_pol(ts_bits=8), backend="numpy")
    pts = eng.write([0], 300)                          # past the 8-bit guard
    r = eng.read([0], pts, req_wts=[-1])
    wts = int(r.wts[0])
    for _ in range(2):                                 # grow 2 -> 4 -> 8
        r = eng.read([0], int(r.rts[0]) + 1, req_wts=[wts])
    pred_before = eng.pred_lease.copy()
    assert int(pred_before[0]) == 8
    wts_before, rts_before = eng.wts.copy(), eng.rts.copy()
    shift = eng.maybe_rebase()
    assert shift > 0
    np.testing.assert_array_equal(eng.pred_lease, pred_before)
    np.testing.assert_array_equal(
        eng.wts, np.maximum(0, wts_before.astype(np.int64) - shift))
    np.testing.assert_array_equal(
        eng.rts, np.maximum(0, rts_before.astype(np.int64) - shift))
    # and the next wasted renewal keeps tuning from where it left off
    r = eng.read([0], int(eng.rts[0]) + 1,
                 req_wts=[int(eng.wts[0])])
    assert int(eng.pred_lease[0]) == 16


# ---------------------------------------------------------------------------
# Predictor across the sharded directory
# ---------------------------------------------------------------------------

def test_sharded_predictor_matches_single_engine_oracle():
    """Random wave streams under the predictor: the reassembled per-block
    predicted-lease table tracks ONE LeaseEngine driven with the same
    per-owner-shard batches, wave by wave (sharding changes the wire,
    never the learned leases)."""
    rng = np.random.default_rng(11)
    pol = _pol(lease=4, lease_max=32)
    d = ShardedLeaseDirectory(16, 4, n_hosts=2, policy=pol, backend="numpy")
    oracle = LeaseEngine(16, policy=pol, backend="numpy")
    pts = 0
    for step in range(50):
        host = step % 2
        if rng.random() < 0.3:
            bids = sorted(rng.choice(16, rng.integers(1, 4),
                                     replace=False).tolist())
            res = d.wave(host, pts, write_bids=bids, tag_writes_with_ts=True)
            for s in sorted({d.owner(b) for b in bids}):
                oracle.write([b for b in bids if d.owner(b) == s], pts)
            pts = res.new_pts
        else:
            bids = sorted(rng.choice(16, rng.integers(1, 5),
                                     replace=False).tolist())
            # renew with the current wts so a post-expiry renewal is
            # exactly the "wasted traffic" signal the predictor feeds on
            req = {b: int(oracle.wts[b]) for b in bids}
            res = d.wave(host, pts, read_groups=[bids], req_wts=req)
            for s in sorted({d.owner(b) for b in bids}):
                part = [b for b in bids if d.owner(b) == s]
                oracle.read(part, pts, req_wts=[req[b] for b in part])
            pts = res.new_pts
        pts += int(rng.integers(0, 10))                # age leases out
        np.testing.assert_array_equal(d.pred_lease, oracle.pred_lease)
        np.testing.assert_array_equal(d.wts, oracle.wts)
        np.testing.assert_array_equal(d.rts, oracle.rts)
    grows = sum(e.stats.pred_grows for e in d.shards)
    shrinks = sum(e.stats.pred_shrinks for e in d.shards)
    assert grows == oracle.stats.pred_grows > 0
    assert shrinks == oracle.stats.pred_shrinks > 0
    assert d.report()["xhost_pred_grows"] == grows


def test_pred_lease_travels_with_page_migration():
    """A migrated page carries the owner's learned lease: the FetchedPage
    snapshot equals the owner-shard prediction at fetch time, and
    ``set_pred_lease`` installs it (clipped to the local bounds)."""
    pol = _pol(lease=4, lease_max=64)
    d = ShardedLeaseDirectory(8, 2, n_hosts=2, policy=pol, backend="numpy",
                              kv_pools=POOLS, kv_dtype=np.float32,
                              block_bytes=16, sanitize=True)
    res = d.wave(0, 0, write_bids=[1], write_tags=[7])
    ts = res.write_ts[1]
    d.defer_publish(0, 1, _page(float(ts)))
    d.flush_deferred(0)
    # grow block 1's prediction with wasted renewals from the writer host
    pts = ts
    for _ in range(3):
        r = d.wave(0, pts, read_groups=[[1]], req_wts={1: ts})
        pts = r.leases[1][1] + 1                       # past the new rts
    assert int(d.pred_lease[1]) > pol.shrink(4)        # it did grow
    res = d.wave(1, pts, fetch_bids=[1])               # host 1 borrows it
    page = res.fetched[1]
    assert page.pred_lease == int(d.pred_lease[1])
    assert (page.wts, page.rts) == res.leases[1]
    # install on a destination engine with tighter bounds: clipped
    dest = LeaseEngine(8, policy=_pol(lease=4, lease_max=8),
                       backend="numpy")
    dest.set_pred_lease([1], page.pred_lease)
    assert int(dest.pred_lease[1]) == min(8, page.pred_lease)


# ---------------------------------------------------------------------------
# Kernel vs mirror: the predictor is backend-invariant
# ---------------------------------------------------------------------------

def test_predictor_bit_identical_pallas_vs_numpy():
    pol = _pol(lease=4, lease_max=32)
    engines = {b: LeaseEngine(8, policy=pol, backend=b)
               for b in ("pallas", "numpy")}
    rng = np.random.default_rng(3)
    script = []
    pts = 0
    for step in range(16):
        idx = sorted(rng.choice(8, 2, replace=False).tolist())
        if step % 4 == 0:
            script.append(("write", idx, pts))
            pts += 5
        else:
            script.append(("read", idx, pts))
            pts += int(rng.integers(0, 9))
    for name, eng in engines.items():
        wts_seen = np.full(8, -1, np.int64)
        for op, idx, p in script:
            if op == "write":
                eng.write(idx, p)
                wts_seen[idx] = -1                     # copies invalidated
            else:
                r = eng.read(idx, p, req_wts=wts_seen[idx].tolist())
                wts_seen[idx] = np.asarray(r.wts, np.int64)
    a, b = engines["pallas"], engines["numpy"]
    np.testing.assert_array_equal(np.asarray(a.wts), np.asarray(b.wts))
    np.testing.assert_array_equal(np.asarray(a.rts), np.asarray(b.rts))
    np.testing.assert_array_equal(a.pred_lease, b.pred_lease)
    assert a.stats.pred_grows == b.stats.pred_grows > 0
    assert a.stats.pred_shrinks == b.stats.pred_shrinks > 0
