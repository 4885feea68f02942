"""Continuous-batching paged decode vs the dense-cache decode: bit-exact.

The serving cluster decodes every token through LeaseEngine pool pages
(``models.decode_step_paged``); the acceptance bar is that this is
*bit-exact* with the dense-cache decode path (``models.decode_step``) for
every attention-cache family -- dense/vlm AND the moe family, whose dual
cache stacks (leading dense layers + moe layers) page through named pools
interleaved in one token row -- over randomized request streams with
mid-stream joins and finishes, page-bounded admission, collision evictions
relocating pinned blocks under an active decode, and ts_bits rebases
firing between ticks.

The differential works off the cluster's trace hook: every admission
records the request's page table and the pool rows backing its prompt,
every decode tick records the batch composition and raw logits.  A dense
*shadow* then replays the exact same schedule -- same batch sizes, same
per-request positions (vector ``cur_idx``), caches seeded from the same
pool bits, each cache stack sliced out of its pool segment
(``models.pool_layout``) -- through ``decode_step`` and asserts the logits
match bit for bit.  Anything the paged path gets wrong (a token row
landing in the wrong page slot, a stack segment at the wrong pool offset,
a gather off by one, an eviction clobbering a pinned page, a rebase
touching payloads) shows up as a bit difference.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import decode_step, init_params, pool_layout
from repro.runtime import Request, ServingCluster

# dense single stack; kimi = dual stacks (1 leading dense + 1 moe layer
# after reduction); arctic = single moe stack (no leading dense layers)
ARCH_BASES = {
    "dense": "tinyllama-1.1b",
    "moe": "kimi-k2-1t-a32b",
    "moe-flat": "arctic-480b",
}


@functools.lru_cache(maxsize=None)
def _arch(name):
    cfg = reduced(get_arch(ARCH_BASES[name]), n_layers=2, d_model=64,
                  vocab=128)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _cluster(arch="dense", **kw):
    cfg, params = _arch(arch)
    kw.setdefault("prefix_block_tokens", 4)
    kw.setdefault("kv_lease", 16)
    kw.setdefault("n_prefix_blocks", 64)
    kw.setdefault("n_decode_pages", 64)
    kw.setdefault("max_pages", 16)
    c = ServingCluster(cfg, lambda: params, **kw)
    c.trace = []
    return c


def _reqs(rng, cfg, n, n_prefixes=2, max_new_hi=4):
    """Random prompts drawn over a few shared system prompts + random
    suffixes and per-request decode budgets (staggered finishes)."""
    prefixes = [rng.integers(1, cfg.vocab, 4 * int(rng.integers(1, 4)))
                .astype(np.int32) for _ in range(n_prefixes)]
    out = []
    for i in range(n):
        p = prefixes[int(rng.integers(0, n_prefixes))]
        suffix = rng.integers(1, cfg.vocab,
                              int(rng.integers(1, 9))).astype(np.int32)
        out.append(Request(i, np.concatenate([p, suffix]),
                           max_new=int(rng.integers(1, max_new_hi + 1))))
    return out


def _replay_dense_shadow(arch, cluster, trace):
    """Re-run the recorded schedule on dense per-request caches seeded from
    the same pool bits and assert bitwise-equal logits every tick.  Each
    cache stack (moe: dk/dv and k/v) is sliced out of its own pool segment
    at the ``pool_layout`` offset.  The dense batch takes the paged step's
    rows, its empty slots included (zero caches, token 0, length 0), so
    both run their matmuls at one shape."""
    cfg, params = _arch(arch)
    stacks = pool_layout(cfg)
    names = [k for s in stacks for k in s.cache_keys]
    bt = cluster.prefix_block_tokens
    hk, dh = cfg.n_kv_heads, cfg.head_dim()
    t_cap = cluster.max_pages * bt
    dec = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
    caches = {}                       # rid -> {cache_key: (L_s, T, hk, dh)}
    ticks = 0
    for ev in trace:
        if ev["ev"] == "admit":
            plen = ev["prompt_len"]
            rows = ev["rows"]                            # (plen, token_row)
            c = {}
            for s in stacks:
                kv = rows[:, s.offset:s.offset + s.token_elems].reshape(
                    plen, 2, s.n_layers, hk, dh)
                k = np.zeros((s.n_layers, t_cap, hk, dh), rows.dtype)
                v = np.zeros_like(k)
                k[:, :plen] = kv[:, 0].transpose(1, 0, 2, 3)
                v[:, :plen] = kv[:, 1].transpose(1, 0, 2, 3)
                c[s.cache_keys[0]] = k
                c[s.cache_keys[1]] = v
            caches[ev["rid"]] = c
        else:
            live = len(ev["rids"])
            pad = (0, ev["rows"] - live)
            cache = {n: jnp.asarray(np.pad(
                np.stack([caches[r][n] for r in ev["rids"]], axis=1),
                [(0, 0), pad, (0, 0), (0, 0), (0, 0)])) for n in names}
            cache2, logits = dec(
                params, cache, jnp.asarray(np.pad(ev["tokens"], [pad, (0, 0)])),
                jnp.asarray(np.pad(ev["lengths"], pad), jnp.int32))
            np.testing.assert_array_equal(
                np.asarray(logits)[:live], ev["logits"],
                err_msg=f"paged decode diverged at tick {ev['tick']} "
                        f"(arch {arch}, rids {ev['rids']})")
            for i, r in enumerate(ev["rids"]):
                caches[r] = {n: np.asarray(cache2[n][:, i]) for n in names}
            ticks += 1
    return ticks


def _check_pool_drained(cluster):
    """Every page released, every pin dropped: no leaks across a run."""
    eng = cluster.prefix_engine
    assert eng.free_page_count() == cluster.n_decode_pages
    assert not cluster._pins and not cluster._reloc_refs
    assert all(not act for act in cluster._active)


@pytest.mark.parametrize("arch", sorted(ARCH_BASES))
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n_replicas", [1, 2])
def test_paged_decode_bit_exact_random_streams(arch, seed, n_replicas):
    """Acceptance: randomized streams with mid-stream joins/finishes are
    bit-exact vs the dense shadow on every paged family (moe's dual cache
    stacks included), and the stream order/outputs line up."""
    cfg, _ = _arch(arch)
    rng = np.random.default_rng(seed)
    cluster = _cluster(arch, n_replicas=n_replicas)
    reqs = _reqs(rng, cfg, 10)
    done, rep = cluster.run(reqs)
    assert all(r.done and len(r.output) == r.max_new for r in done)
    ticks = _replay_dense_shadow(arch, cluster, cluster.trace)
    assert ticks > 0
    _check_pool_drained(cluster)
    assert rep["prefix_block_hits"] > 0          # prefixes really shared
    assert rep["kv_tokens_appended"] > 0         # decode wrote pool pages
    # per-stack occupancy ledger CONSISTENCY: serving appends full
    # interleaved rows, so every stack must see exactly the same token
    # traffic (whether the bits landed at the right offsets is what the
    # dense-shadow differential above proves)
    for s in pool_layout(cfg):
        assert rep[f"pool_tokens_appended_{s.pool}"] \
            == rep["kv_tokens_appended"]


@pytest.mark.parametrize("arch", ["dense", "moe"])
def test_admission_bounded_by_free_pages_joins_mid_batch(arch):
    """A tiny page budget forces the scheduler to defer admission until a
    running request frees its pages -- the joiner lands mid-batch and the
    whole stream is still bit-exact."""
    cfg, _ = _arch(arch)
    rng = np.random.default_rng(3)
    # each request needs ceil((8+4)/4) = 3 pages; budget fits two at once
    cluster = _cluster(arch, n_replicas=1, n_decode_pages=6,
                       n_prefix_blocks=64)
    reqs = [Request(i, rng.integers(1, cfg.vocab, 8).astype(np.int32),
                    max_new=2 + 2 * (i % 2)) for i in range(4)]
    done, rep = cluster.run(reqs)
    assert all(r.done and len(r.output) == r.max_new for r in done)
    assert rep["paged_admission_deferrals"] > 0
    assert rep["paged_mid_batch_admissions"] > 0
    assert rep["pool_page_peak"] <= 6
    _replay_dense_shadow(arch, cluster, cluster.trace)
    _check_pool_drained(cluster)


@pytest.mark.parametrize("arch", ["dense", "moe"])
def test_collision_eviction_relocates_pinned_blocks_mid_decode(arch):
    """A colliding admission re-tags a block an active decode still reads:
    the payload (every cache stack's segment) must relocate to a fresh page
    (zero messages), the active page table remap, and the decode stay
    bit-exact."""
    cfg, _ = _arch(arch)
    rng = np.random.default_rng(4)
    cluster = _cluster(arch, n_replicas=1, n_prefix_blocks=1, max_batch=2)
    pa = rng.integers(1, cfg.vocab, 6).astype(np.int32)   # 1 block + tail
    pb = rng.integers(1, cfg.vocab, 6).astype(np.int32)   # same bid, new tag
    # warm the pool so request A's prefix block is covered (pinned)
    cluster.run([Request(0, pa, max_new=1)])
    a = Request(1, pa, max_new=6)              # long decode, pins block 0
    # block-less filler (prompt < one chunk) holds the second batch slot so
    # the evictor can only join after it finishes -- mid-decode for A
    filler = Request(2, rng.integers(1, cfg.vocab, 3).astype(np.int32),
                     max_new=2)
    b = Request(3, pb, max_new=2)              # evicts block 0 mid-decode
    done, rep = cluster.run([a, filler, b])
    assert all(r.done for r in done)
    assert rep["pinned_relocations"] >= 1
    assert rep["prefix_evictions"] >= 1
    assert rep["paged_mid_batch_admissions"] >= 1
    _replay_dense_shadow(arch, cluster, cluster.trace)
    _check_pool_drained(cluster)


@pytest.mark.parametrize("arch", ["dense", "moe"])
def test_rebase_mid_decode_shifts_metadata_only(arch):
    """Satellite: ``maybe_rebase()`` firing between decode ticks must leave
    page payloads intact and shift only lease metadata -- live page tables
    keep decoding bit-exactly across the rebase."""
    cfg, _ = _arch(arch)
    rng = np.random.default_rng(5)
    cluster = _cluster(arch, n_replicas=2, ts_bits=5, kv_lease=4)
    reqs = _reqs(rng, cfg, 16, max_new_hi=6)
    done, rep = cluster.run(reqs)
    assert all(r.done for r in done)
    assert rep["prefix_rebases"] >= 1            # rebases really fired
    assert rep["decode_renewals"] > 0            # short leases renew in-flight
    _replay_dense_shadow(arch, cluster, cluster.trace)
    _check_pool_drained(cluster)
    # every surviving lease is under the rebased width
    for rep_ in cluster.replicas:
        assert all(r < (1 << 5) for _, r, _t in rep_.kv_leases.values())


def test_decode_holds_leases_and_ledgers_renewals():
    """Shared prefix blocks stay leased for the whole decode: ticks past
    the lease renew data-less (ONE dispatch), unexpired ticks are local
    hits, and the ledger separates the decode-time traffic."""
    cfg, _ = _arch("dense")
    rng = np.random.default_rng(6)
    cluster = _cluster(n_replicas=1, kv_lease=3)
    prefix = rng.integers(1, cfg.vocab, 8).astype(np.int32)
    cluster.run([Request(0, np.concatenate(
        [prefix, rng.integers(1, cfg.vocab, 3).astype(np.int32)]),
        max_new=1)])
    reads0 = cluster.prefix_engine.stats.read_ops
    cluster.run([Request(1, np.concatenate(
        [prefix, rng.integers(1, cfg.vocab, 3).astype(np.int32)]),
        max_new=10)])
    rep = cluster.coherence_report()
    assert rep["decode_renewals"] > 0
    assert rep["decode_local_hits"] > 0
    assert rep["decode_block_reads"] > 0
    # renewals batch: strictly fewer dispatches than (ticks x blocks)
    assert (cluster.prefix_engine.stats.read_ops - reads0
            <= 1 + rep["decode_renewals"])
    _replay_dense_shadow("dense", cluster, cluster.trace)


@pytest.mark.parametrize("arch", sorted(ARCH_BASES))
def test_decode_runs_one_shape_as_batch_shrinks(arch):
    """Streams finish one by one, and every decode tick still runs the one
    program compiled at ``max_batch`` rows: the empty slots append nothing
    (the dense shadow stays bit-exact) and emit nothing.  moe decodes its
    live batch, one shape for each size it meets."""
    cfg, _ = _arch(arch)
    rng = np.random.default_rng(3)
    cluster = _cluster(arch, n_replicas=1, max_batch=4)
    reqs = [Request(i, rng.integers(1, cfg.vocab, 5).astype(np.int32),
                    max_new=2 + 2 * i) for i in range(4)]
    done, rep = cluster.run(reqs)
    assert [len(r.output) for r in done] == [r.max_new for r in reqs]
    sizes = {len(ev["rids"]) for ev in cluster.trace if ev["ev"] == "tick"}
    assert sizes == {1, 2, 3, 4}
    shapes = cluster._decode_paged_fn._cache_size()
    assert shapes == (len(sizes) if cfg.family == "moe" else 1)
    _replay_dense_shadow(arch, cluster, cluster.trace)
    _check_pool_drained(cluster)


def test_decode_pages_reserved_counts_page_tables():
    """``decode_pages_reserved`` sums, over the decode ticks, each stream's
    reserved pages (prompt plus ``max_new``): never fewer than the pages in
    use (``decode_block_reads``), and as many when every reserved page
    holds a token from the first tick on."""
    cfg, _ = _arch("dense")
    rng = np.random.default_rng(9)
    cluster = _cluster(n_replicas=1)
    bt = cluster.prefix_block_tokens
    # long outputs reserve pages that the early ticks have not written
    _, rep = cluster.run(_reqs(rng, cfg, 4, max_new_hi=3 * bt))
    assert rep["decode_pages_reserved"] > rep["decode_block_reads"] > 0
    # prompt plus output within one page: that page is in use every tick
    cluster = _cluster(n_replicas=1)
    _, rep = cluster.run([
        Request(i, rng.integers(1, cfg.vocab, 1).astype(np.int32),
                max_new=bt - 1) for i in range(3)])
    assert rep["decode_pages_reserved"] == rep["decode_block_reads"] > 0


def test_moe_dual_stack_pool_layout_matches_engine():
    """The models' static stack offsets (pool_layout) and the engine's
    interleaved token row agree, and both stacks share the block table,
    the free list, and the validity bitmap -- one id, one transition."""
    cfg, _ = _arch("moe")
    cluster = _cluster("moe", n_replicas=1)
    eng = cluster.prefix_engine
    stacks = pool_layout(cfg)
    assert [s.pool for s in stacks] == ["dense", "moe"] == eng.pool_names
    assert cfg.first_dense_layers >= 1           # really dual stacks
    for s in stacks:
        assert eng.pool_offset(s.pool) == s.offset
        assert eng.pool_token_elems(s.pool) == s.token_elems
    assert eng.kv_token_row == sum(eng.pool_token_row(s.pool)
                                   for s in stacks)
    # one write publishes BOTH stacks; one invalidate frees both
    hk, dh = cfg.n_kv_heads, cfg.head_dim()
    bt = cluster.prefix_block_tokens
    rng = np.random.default_rng(0)
    blocks = {s.pool: rng.normal(size=(1, bt, 2, s.n_layers * hk, dh))
              .astype(np.float32) for s in stacks}
    writes0 = eng.stats.kv_blocks_written
    eng.write_kv([3], blocks)
    assert eng.stats.kv_blocks_written == writes0 + 1 and eng.kv_ok(3)
    out = eng.read_kv([3])
    for s in stacks:
        np.testing.assert_allclose(np.asarray(out[s.pool], np.float32),
                                   blocks[s.pool], rtol=0.02, atol=0.02)
        # the windowed per-stack gather sees the same bits
        np.testing.assert_array_equal(
            np.asarray(eng.read_kv([3], pool=s.pool)),
            np.asarray(out[s.pool]))
    eng.invalidate_kv([3])
    assert not eng.kv_ok(3)


def test_dense_wave_fallback_families_still_serve():
    """Only ssm/hybrid keep the fixed-wave dense-cache path (their
    recurrent states are not block-addressable); the lease metadata
    protocol still runs."""
    for base in ("mamba2-130m", "zamba2-2.7b"):
        cfg = reduced(get_arch(base))
        params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
        cluster = ServingCluster(cfg, lambda: params, n_replicas=1,
                                 prefix_block_tokens=4, cache_len=32)
        assert not cluster.paged
        rng = np.random.default_rng(7)
        reqs = [Request(i, rng.integers(1, cfg.vocab, 8).astype(np.int32),
                        max_new=2) for i in range(2)]
        done, rep = cluster.run(reqs)
        assert all(r.done and len(r.output) == 2 for r in done)
        assert rep["prefix_block_hits"] + rep["prefix_block_misses"] > 0


def test_paged_decode_with_sanitizer_enabled():
    """The full continuous-batching run under TARDIS_SANITIZE semantics:
    every engine transition is shadow-checked, the stream stays bit-exact
    against the dense shadow, and the report ledgers the check count."""
    cfg, _ = _arch("dense")
    rng = np.random.default_rng(0)
    cluster = _cluster("dense", n_replicas=1, sanitize=True)
    reqs = _reqs(rng, cfg, 8)
    done, rep = cluster.run(reqs)
    assert all(r.done and len(r.output) == r.max_new for r in done)
    assert _replay_dense_shadow("dense", cluster, cluster.trace) > 0
    _check_pool_drained(cluster)
    assert rep["sanitize_checks"] > 0
