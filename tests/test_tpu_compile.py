"""The served path's Pallas kernels compile for a TPU v5e that is described,
not attached.

Interpret mode (every other test) accepts block shapes and in-kernel
reshapes that the chip's compiler refuses, so these tests lower and compile
each kernel of the paged serving path against shapes only, at the widths
tinyllama-1.1b serves with: the four ``tardis_lease`` table kernels at
``ServingCluster``'s default table and at the serve CLI's, the page gather
and the decode append at the full token row, the paged flash-decode kernel
at the model's head layout (and at the serving benchmark's glm4-9b and
mistral-nemo-12b pages, and in both of kimi-k2's cache stacks), and one
``decode_step_paged`` cut to 2 layers.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.tardis_lease import kernel as lease_kernel
from repro.kernels.tardis_lease import ops as lease_ops
from repro.models import abstract_params, decode_step_paged, pool_layout

TINYLLAMA = get_arch("tinyllama-1.1b")
CHUNK = 16                                  # tokens per pool page
# ServingCluster's default table (4096 prefix blocks + 512 decode pages)
# and the serve CLI's (4096 + 256): 36 and 34 rows of 128 blocks
TABLE_BLOCKS = (4608, 4352)
BATCH, PAGES = 4, 32                        # decode slots x page-table length


def _token_row(cfg) -> int:
    last = pool_layout(cfg)[-1]
    return last.offset + -(-last.token_elems // 128) * 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, n_kernels: int = 1):
    text = jax.jit(fn).lower(*args).compile().as_text()
    # a native Mosaic kernel is a custom call; interpret mode leaves none
    assert text.count("tpu_custom_call") >= n_kernels


@pytest.mark.parametrize("n_blocks", TABLE_BLOCKS)
@pytest.mark.parametrize("op,lease", [
    ("lease_table", "static"), ("lease_table", "predicted"),
    ("lease_table_many", "static"), ("lease_table_many", "predicted"),
    ("rowmax_advance", "static"),
])
def test_lease_table_kernels_compile(one_chip, n_blocks, op, lease):
    n = (n_blocks,)
    t = _shape(one_chip, n)
    lease_arg = t if lease == "predicted" else _shape(one_chip, ())
    if op == "lease_table":
        _compile(lambda w, r, q, m, ls: lease_ops.masked_lease_check(
            w, r, q, m, jnp.int32(3), ls), t, t, t, t, lease_arg)
    elif op == "lease_table_many":
        masks = _shape(one_chip, (3, n_blocks))
        pts = _shape(one_chip, (3,))
        _compile(lambda w, r, q, m, p, ls: lease_ops.masked_lease_check_many(
            w, r, q, m, p, ls), t, t, t, masks, pts, lease_arg)
    else:
        _compile(lambda w, r, m: lease_ops.write_advance(
            w, r, m, jnp.int32(3)), t, t, t, n_kernels=2)


@pytest.mark.parametrize("op", ["gather", "scatter"])
@pytest.mark.parametrize("window", ["row", "layer_kv"])
def test_pool_row_kernels_compile(one_chip, op, window):
    """Page gather and decode append over the 4608-block bf16 pool's token
    rows: the whole row, and one layer's K window inside it."""
    width = _token_row(TINYLLAMA)
    assert width == 11264
    hkd = TINYLLAMA.n_kv_heads * TINYLLAMA.head_dim()
    col_lo, w = (0, width) if window == "row" else (5 * hkd, hkd)
    pool = _shape(one_chip, (TABLE_BLOCKS[0] * CHUNK, width), jnp.bfloat16)
    idx = _shape(one_chip, (3 * CHUNK,))
    if op == "gather":
        _compile(lambda p, i: lease_kernel.gather_rows(
            p, i, col_lo=col_lo, width=w), pool, idx)
    else:
        rows = _shape(one_chip, (BATCH, w), jnp.bfloat16)
        _compile(lambda p, i, r: lease_kernel.scatter_rows(
            p, i, r, col_lo=col_lo), pool, _shape(one_chip, (BATCH,)), rows)


KIMI = get_arch("kimi-k2-1t-a32b")

# (config, cache stack, layer, page tokens, pool blocks, decode slots,
# page-table length): tinyllama at the serve CLI's pages; the serving
# benchmark's glm4-9b (20 layers) and mistral-nemo-12b (10 layers) at their
# 256-token pages, pools and batches; kimi-k2's 896-wide windows in its
# dense stack and in its moe stack, whose segment starts at 1792
PAGED_CELLS = [
    pytest.param(TINYLLAMA, 0, 0, CHUNK, TABLE_BLOCKS[0], BATCH, PAGES,
                 id="0"),
    pytest.param(TINYLLAMA, 0, TINYLLAMA.n_layers - 1, CHUNK,
                 TABLE_BLOCKS[0], BATCH, PAGES, id="21"),
    pytest.param(dataclasses.replace(get_arch("glm4-9b"), n_layers=20), 0,
                 9, 256, 296, 32, 9, id="glm4-9b"),
    pytest.param(dataclasses.replace(get_arch("mistral-nemo-12b"),
                                     n_layers=10), 0, 4, 256, 544, 16, 19,
                 id="mistral-nemo-12b"),
    pytest.param(KIMI, 0, 0, CHUNK, 512, BATCH, PAGES, id="kimi-dense"),
    pytest.param(KIMI, 1, 30, CHUNK, 512, BATCH, PAGES, id="kimi-moe"),
]


@pytest.mark.parametrize("cfg,stack,layer,chunk,n_blocks,batch,pages",
                         PAGED_CELLS)
def test_paged_decode_attention_compiles(one_chip, cfg, stack, layer, chunk,
                                         n_blocks, batch, pages):
    """One layer's paged flash-decode at the default scoped-VMEM limit (no
    libtpu flag): its blocks are the layer's (chunk, hk*dh) K and V
    windows, not the (chunk, token_row) page row."""
    hk, dh = cfg.n_kv_heads, cfg.head_dim()
    spec = pool_layout(cfg)[stack]
    pool = _shape(one_chip, (n_blocks * chunk, _token_row(cfg)),
                  jnp.bfloat16)
    q = _shape(one_chip, (batch, 1, cfg.n_heads, dh), jnp.float32)
    cur = _shape(one_chip, (batch, 1, hk, dh), jnp.bfloat16)
    _compile(lambda q_, k_, v_, p, pr, ln: paged_decode_attention(
        q_, k_, v_, p, pr, ln, chunk=chunk, k_off=layer * hk * dh,
        v_off=(spec.n_layers + layer) * hk * dh, hkv=hk,
        pool_off=spec.offset),
        q, cur, cur, pool, _shape(one_chip, (batch, pages)),
        _shape(one_chip, (batch,)))


def test_decode_step_paged_compiles(one_chip):
    """One whole decode step at tinyllama widths, cut to 2 layers: paged
    flash-decode per layer plus the single append."""
    cfg = dataclasses.replace(TINYLLAMA, n_layers=2)
    params = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        abstract_params(cfg, jnp.float32))
    pool = _shape(one_chip, (TABLE_BLOCKS[1] * CHUNK, _token_row(cfg)),
                  jnp.bfloat16)
    _compile(lambda p, pl_, pr, ln, tk: decode_step_paged(
        cfg, p, pl_, pr, ln, tk, chunk=CHUNK, use_kernel=True),
        params, pool, _shape(one_chip, (BATCH, PAGES)),
        _shape(one_chip, (BATCH,)), _shape(one_chip, (BATCH, 1)),
        n_kernels=cfg.n_layers + 1)
