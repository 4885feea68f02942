"""Pallas kernels vs. pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.kernels.decode_attention.kernel import paged_block_width
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.tardis_lease.ops import (append_rows, gather_blocks,
                                            lease_check,
                                            masked_lease_check,
                                            masked_lease_check_many,
                                            write_advance)
from repro.kernels.tardis_lease.ref import (append_rows_ref,
                                            gather_blocks_ref,
                                            lease_check_ref,
                                            masked_lease_check_many_ref,
                                            masked_lease_check_ref,
                                            write_advance_ref)
from repro.models.decoding import PAGED_FAMILIES, pool_layout

KEY = jax.random.PRNGKey(0)
TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (32, 512), (5, 2048), (16, 80)])
def test_rmsnorm_kernel(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), dtype)
    out = rmsnorm(x, w, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,s,h,hk,d", [
    (1, 128, 4, 4, 128),         # MHA, aligned head dim
    (2, 256, 8, 2, 64),          # GQA, padded head dim
    (1, 256, 4, 1, 80),          # MQA, zamba-style 80-dim heads
])
def test_flash_attention_kernel(b, s, h, hk, d, causal, dtype):
    q = jax.random.normal(KEY, (b, s, h, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hk, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hk, d), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("kv_len", [1, 100, 512, 1024])
@pytest.mark.parametrize("b,h,hk,d,t", [(2, 8, 2, 64, 1024),
                                        (1, 4, 4, 128, 512)])
def test_decode_attention_kernel(b, h, hk, d, t, kv_len):
    q = jax.random.normal(KEY, (b, 1, h, d))
    kc = jax.random.normal(jax.random.PRNGKey(1), (b, t, hk, d))
    vc = jax.random.normal(jax.random.PRNGKey(2), (b, t, hk, d))
    out = decode_attention(q, kc, vc, jnp.int32(kv_len), interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 4, 16, 32, 32),
    (1, 96, 2, 32, 16, 16),      # padded final chunk path
    (1, 64, 8, 64, 128, 64),     # mamba2-130m-like dims
])
def test_ssd_scan_kernel(b, s, h, p, n, chunk):
    x = jax.random.normal(KEY, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (h,)) * 0.5)
    B = jax.random.normal(jax.random.PRNGKey(5), (b, s, n))
    C = jax.random.normal(jax.random.PRNGKey(6), (b, s, n))
    D = jnp.ones((h,))
    y1, s1 = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    y2, s2 = ssd_scan_ref(x, dt, A, B, C, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [7, 128, 1000, 4096])
@pytest.mark.parametrize("pts,lease", [(0, 10), (55, 10), (1000, 64)])
def test_tardis_lease_kernel(n, pts, lease):
    rng = np.random.default_rng(n)
    wts = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
    rts = jnp.maximum(wts, jnp.asarray(rng.integers(0, 120, n), jnp.int32))
    req = jnp.where(jnp.asarray(rng.random(n) < 0.5), wts, wts - 1)
    out = lease_check(wts, rts, req, pts, lease, interpret=True)
    ref = lease_check_ref(wts, rts, req, jnp.int32(pts), jnp.int32(lease))
    for k in ("new_rts", "renew_ok", "expired", "write_ts"):
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]),
                                      err_msg=k)


@pytest.mark.parametrize("n", [7, 128, 1000])
@pytest.mark.parametrize("pts", [0, 55])
def test_tardis_masked_ops(n, pts):
    """The engine's two transitions (masked lease pass + write jump-ahead)
    against the protocol-oracle refs, including pts advance."""
    rng = np.random.default_rng(n + pts)
    wts = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
    rts = jnp.maximum(wts, jnp.asarray(rng.integers(0, 120, n), jnp.int32))
    req = jnp.where(jnp.asarray(rng.random(n) < 0.5), wts, wts - 1)
    mask = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    out = masked_lease_check(wts, rts, req, mask, pts, 10, interpret=True)
    ref = masked_lease_check_ref(wts, rts, req, mask, jnp.int32(pts),
                                 jnp.int32(10))
    for k in ("new_rts", "renew_ok", "expired", "write_ts", "new_pts"):
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]),
                                      err_msg=k)
    w1, r1, t1 = write_advance(wts, rts, mask, pts, interpret=True)
    w2, r2, t2 = write_advance_ref(wts, rts, mask, jnp.int32(pts))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(t1) == int(t2)


# toy shapes: where hk*d is no multiple of 128 (32, 16) each page's whole
# token row is DMA'd and the layer's columns sliced in VMEM
_TOY_SHAPES = [
    (3, 6, 2, 16, 4, 8, 3),
    (2, 4, 4, 32, 8, 16, 4),     # MHA-style, bigger pages
    (1, 8, 1, 16, 16, 4, 2),     # MQA
]


@pytest.mark.parametrize("layers,layer,b,h,hk,d,chunk,nb,p", [
    (layers, layer) + shape
    for layers, layer in [(2, 0), (2, 1), (1, 0)] for shape in _TOY_SHAPES
] + [
    # lane-aligned column windows: a middle layer, K and V off column 0
    (3, 1, 3, 4, 1, 128, 16, 12, 4),
    (4, 2, 2, 8, 2, 128, 16, 10, 5),
    # two stacks (kimi's layout cut down): layer 2 of the second stack,
    # whose segment starts at the lane-padded end of the first
    ((1, 3), 2, 2, 8, 1, 128, 16, 10, 5),
])
def test_paged_decode_attention_kernel(b, h, hk, d, chunk, nb, p, layers,
                                       layer):
    """Paged flash-decode (page tables drive the K/V DMA) vs the
    gather-then-reference oracle, across ragged per-request lengths.

    Where the kernel reads column windows, the pool it is given holds NaN
    in every other layer's (and stack's) columns and in every reserved page
    past a request's length; the oracle reads the pool unpoisoned.  A read
    of a foreign column or any use of an unwritten page turns the output
    NaN."""
    rng = np.random.default_rng(b * 100 + h)
    stacks = layers if isinstance(layers, tuple) else (layers,)
    hkd = hk * d
    pool_off = sum(-(-2 * n * hkd // 128) * 128 for n in stacks[:-1])
    te = pool_off + 2 * stacks[-1] * hkd
    token_row = -(-te // 128) * 128
    pool = rng.standard_normal((nb * chunk, token_row)).astype(np.float32)
    k_off, v_off = layer * hkd, (stacks[-1] + layer) * hkd
    page_rows = rng.integers(0, nb, (b, p))
    lengths = rng.integers(0, p * chunk, b)
    windows = paged_block_width(hkd, pool_off + k_off, pool_off + v_off,
                                token_row) == hkd
    assert windows == (hkd % 128 == 0)
    if windows:
        # distinct pages per request, one length on a page boundary
        page_rows = rng.permutation(nb)[:b * p].reshape(b, p)
        lengths[0] = chunk
        poisoned = np.full_like(pool, np.nan)
        for lo in (pool_off + k_off, pool_off + v_off):
            poisoned[:, lo:lo + hkd] = pool[:, lo:lo + hkd]
        for bb in range(b):
            for pg in page_rows[bb, -(-lengths[bb] // chunk):]:
                poisoned[pg * chunk:(pg + 1) * chunk] = np.nan
    else:
        poisoned = pool
    page_rows = jnp.asarray(page_rows, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((b, 1, hk, d)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((b, 1, hk, d)), jnp.float32)
    out = paged_decode_attention(q, ck, cv, jnp.asarray(poisoned), page_rows,
                                 lengths, chunk=chunk, k_off=k_off,
                                 v_off=v_off, hkv=hk, pool_off=pool_off,
                                 interpret=True)
    ref = paged_decode_attention_ref(q, ck, cv, jnp.asarray(pool), page_rows,
                                     lengths, chunk=chunk,
                                     k_off=pool_off + k_off,
                                     v_off=pool_off + v_off, hkv=hk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", sorted(
    n for n, c in ARCHS.items() if c.family in PAGED_FAMILIES))
def test_paged_configs_take_column_windows(arch):
    """Every served paged configuration's layers, in every cache stack,
    read their own K/V column windows, never the whole token row."""
    cfg = ARCHS[arch]
    hkd = cfg.n_kv_heads * cfg.head_dim()
    stacks = pool_layout(cfg)
    token_row = stacks[-1].offset + -(-stacks[-1].token_elems // 128) * 128
    for s in stacks:
        for li in range(s.n_layers):
            assert paged_block_width(
                hkd, s.offset + li * hkd, s.offset + (s.n_layers + li) * hkd,
                token_row) == hkd, (arch, s.pool, li)


@pytest.mark.parametrize("n,w,rows_w", [(8, 256, 256), (16, 512, 300)])
def test_append_rows_scatter_kernel(n, w, rows_w):
    """The token-append scatter: written rows land at their ids, every
    other row keeps its bits (in/out aliasing), last write wins."""
    rng = np.random.default_rng(n)
    pool = jnp.asarray(rng.standard_normal((n, w)), jnp.float32)
    idx = jnp.asarray([2, 0, n - 1, 2], jnp.int32)       # duplicate id
    rows = jnp.asarray(rng.standard_normal((4, rows_w)), jnp.float32)
    ref = np.asarray(append_rows_ref(pool, idx, rows))
    out = append_rows(pool, idx, rows, interpret=True)   # donates pool
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("col_lo,w", [(0, 256), (128, 128)])
def test_scatter_rows_skips_ids_past_pool(col_lo, w):
    """An id at or past the pool's end writes nothing (an empty decode
    slot's row), as XLA's own scatter drops it; the other rows land."""
    from repro.kernels.tardis_lease.kernel import scatter_rows
    rng = np.random.default_rng(w)
    pool = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    idx = jnp.asarray([5, 64, 0, 64, 63], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((5, w)), jnp.float32)
    ref = np.asarray(pool.at[idx, col_lo:col_lo + w].set(rows))
    out = scatter_rows(pool, idx, rows, col_lo=col_lo, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("col_lo,w", [(0, 256), (128, 128)])
def test_scatter_rows_skips_negative_ids(col_lo, w):
    """A negative id (a prefill bucket's pad row past its prompt) writes
    nothing, in the kernel, its oracle and the engine's numpy mirror; the
    other ids write what they wrote with the negative ones left out."""
    from repro.core.lease_engine import LeaseEngine
    from repro.kernels.tardis_lease.kernel import scatter_rows
    rng = np.random.default_rng(w + 1)
    pool = rng.standard_normal((64, 256)).astype(np.float32)
    idx = np.array([5, -1, 0, -64, 63, 5, -3], np.int32)
    rows = rng.standard_normal((idx.size, w)).astype(np.float32)
    keep = idx >= 0
    want = pool.copy()
    for i, r in zip(idx[keep], rows[keep]):      # last write wins
        want[i, col_lo:col_lo + w] = r
    outs = [
        scatter_rows(jnp.asarray(pool), jnp.asarray(idx), jnp.asarray(rows),
                     col_lo=col_lo, interpret=True),
        scatter_rows(jnp.asarray(pool), jnp.asarray(idx[keep]),
                     jnp.asarray(rows[keep]), col_lo=col_lo, interpret=True),
        append_rows_ref(jnp.asarray(pool), idx, rows, col_lo, w)]
    if col_lo == 0:
        # the engine's full-row append: 16 blocks of 4 token rows of 256
        for backend in ("pallas", "numpy"):
            eng = LeaseEngine(16, lease=8, backend=backend,
                              kv_pools={"kv": (4, 2, 8, 16)},
                              kv_dtype=np.float32)
            eng.set_kv_rows(jnp.asarray(pool))
            eng.append_kv(idx, rows)
            assert eng.stats.kv_tokens_appended == int(keep.sum())
            assert [b for b in range(16) if eng.kv_ok(b)] == [0, 1, 15]
            outs.append(eng.kv_rows_view())
    for out in outs:
        np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("n", [4352, 4608])     # 34 and 36 rows of 128
@pytest.mark.parametrize("lease", ["static", "predicted"])
def test_tardis_table_kernels_unaligned_rows(n, lease):
    """Serving-size tables whose row count is not a multiple of the 8-row
    block: the padded kernels stay bit-identical to the protocol refs."""
    rng = np.random.default_rng(n)
    wts = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
    rts = jnp.maximum(wts, jnp.asarray(rng.integers(0, 120, n), jnp.int32))
    req = jnp.where(jnp.asarray(rng.random(n) < 0.5), wts, wts - 1)
    masks = jnp.asarray(rng.integers(0, 2, (3, n)), jnp.int32)
    pts = jnp.asarray([40, 55, 70], jnp.int32)
    ls = (jnp.asarray(rng.integers(4, 64, n), jnp.int32)
          if lease == "predicted" else jnp.int32(10))
    out = masked_lease_check(wts, rts, req, masks[0], pts[0], ls,
                             interpret=True)
    ref = masked_lease_check_ref(wts, rts, req, masks[0], pts[0], ls)
    for k in ("new_rts", "renew_ok", "expired", "write_ts", "new_pts"):
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]),
                                      err_msg=k)
    out = masked_lease_check_many(wts, rts, req, masks, pts, ls,
                                  interpret=True)
    ref = masked_lease_check_many_ref(wts, rts, req, masks, pts, ls)
    for k in ("new_rts", "renew_ok", "expired", "write_ts", "new_pts"):
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]),
                                      err_msg=k)
    w1, r1, t1 = write_advance(wts, rts, masks[1], pts[1], interpret=True)
    w2, r2, t2 = write_advance_ref(wts, rts, masks[1], pts[1])
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(t1) == int(t2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_rows", [32, 12])
def test_moe_dual_stack_column_window_gather_scatter(dtype, n_rows):
    """One stack's column window of the moe family's interleaved token row
    (leading dense stack, then moe stack): the gather returns exactly that
    window, and the append writes it while every other column and row --
    the neighbor stack's segment included -- keeps its bits."""
    from repro.configs import get_arch, reduced
    from repro.models import pool_layout
    stacks = pool_layout(reduced(get_arch("kimi-k2-1t-a32b")))
    assert [s.pool for s in stacks] == ["dense", "moe"]
    last = stacks[-1]
    token_row = last.offset + -(-last.token_elems // 128) * 128
    rng = np.random.default_rng(n_rows)
    pool = jnp.asarray(rng.standard_normal((n_rows, token_row)), dtype)
    idx = jnp.asarray([3, 0, n_rows - 1, 3, 5], jnp.int32)    # duplicate id
    for s in stacks:
        width = -(-s.token_elems // 128) * 128
        got = gather_blocks(pool, idx, col_lo=s.offset, width=width,
                            interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(gather_blocks_ref(
                pool, idx, s.offset, width)))
        rows = jnp.asarray(rng.standard_normal((5, s.token_elems)), dtype)
        ref = np.asarray(append_rows_ref(pool, idx, rows, s.offset, width))
        out = append_rows(jnp.array(pool), idx, rows, col_lo=s.offset,
                          width=width, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), ref)


def test_decode_attention_routing():
    """models.attention.decode_attention routes eligible GQA shapes through
    the Pallas flash-decode kernel (interpret fallback off-TPU) and keeps
    the dense einsum as the reference for everything else."""
    from repro.models import attention as A
    rng = np.random.default_rng(0)
    b, h, hk, d, t = 1, 4, 2, 64, 2048
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, t, hk, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, t, hk, d)), jnp.float32)
    on_tpu = jax.default_backend() == "tpu"
    # the auto-route fires only where the kernel compiles (TPU)
    assert A._kernel_eligible(q, kc, jnp.int32(100),
                              A.DECODE_KERNEL_MIN_T) == on_tpu
    # small caches stay on the einsum; vector kv_len is the paged path's
    assert not A._kernel_eligible(q, kc[:, :512], jnp.int32(9), 2048)
    assert not A._kernel_eligible(q, kc, jnp.asarray([100]), 2048)
    # forcing the route off-TPU takes the interpret fallback
    routed = A.decode_attention(q, kc, vc, jnp.int32(100), use_kernel=True)
    ref = A.decode_attention(q, kc, vc, jnp.int32(100), use_kernel=False)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_lease_kernel_matches_simulator_rules():
    """The kernel's rules ARE Table III: cross-check against protocol fns."""
    from repro.core import protocol as P
    wts = jnp.asarray([5, 5, 9], jnp.int32)
    rts = jnp.asarray([8, 20, 9], jnp.int32)
    req = jnp.asarray([5, 4, 9], jnp.int32)
    out = lease_check(wts, rts, req, 10, 10, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out["new_rts"]),
        np.asarray(P.lease_extend(wts, rts, jnp.int32(10), jnp.int32(10))))
    assert out["write_ts"] == 21     # jump past the longest lease
