"""Admission writes the prefill's KV into its pool pages on the device.

The jitted ``prefill`` / ``prefill_suffix`` programs return, beside the
cache and the logits, positions ``[skip, cache_len)`` of the cache as full
pool token rows; admission scatters them with id -1 for the bucket's pad
rows past the prompt, which the scatter skips.  The reference here is the
packing the serving loop used to make on the host: every stack's layers'
K then V, read out of the cache as float32, at the stack's pool offset,
zeros in the lane padding, cast to the pool's dtype.  The pool rows of
positions ``[skip, plen)`` must match it bit for bit, and every other row
must keep its bits (the region blocks the admission writes back aside).
"""
import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.policy import CoherencePolicy
from repro.models import init_params, pool_layout
from repro.runtime import Request, ServingCluster

BT = 4                                   # tokens a page
ARCH_BASES = {"dense": "tinyllama-1.1b", "moe": "kimi-k2-1t-a32b"}


@functools.lru_cache(maxsize=None)
def _arch(name):
    cfg = reduced(get_arch(ARCH_BASES[name]), n_layers=2, d_model=64,
                  vocab=128)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _host_rows(stacks, token_row, dtype, cache, lo, hi):
    """The host packing admission used before it stayed on the device."""
    m = hi - lo
    rows = np.zeros((m, token_row), np.float32)
    for s in stacks:
        k = np.asarray(cache[s.cache_keys[0]][:, 0, lo:hi])
        v = np.asarray(cache[s.cache_keys[1]][:, 0, lo:hi])
        kr = k.transpose(1, 0, 2, 3).reshape(m, -1)
        vr = v.transpose(1, 0, 2, 3).reshape(m, -1)
        rows[:, s.offset:s.offset + s.token_elems] = \
            np.concatenate([kr, vr], axis=1)
    return np.asarray(jnp.asarray(rows).astype(dtype))


class _Admitter:
    """Admits one request at a time into a one-replica cluster and checks
    what the admission left in the pool."""

    def __init__(self, arch, backend):
        self.cfg, params = _arch(arch)
        self.cluster = ServingCluster(
            self.cfg, lambda: params, n_replicas=1, prefix_block_tokens=BT,
            policy=CoherencePolicy(lease=16), n_prefix_blocks=64,
            n_decode_pages=64, max_pages=16, max_batch=4,
            prefix_backend=backend)
        self.eng = self.cluster.prefix_engine
        self.stacks = pool_layout(self.cfg)
        self.act = []
        self.prefills = []                  # (cache, skip, cache_len)
        self.written = []                   # region blocks written back
        c = self.cluster
        full, suffix, write_kv = c._prefill_fn, c._psuffix_fn, \
            self.eng.write_kv

        def prefill(p, b, cl, li):
            out = full(p, b, cl, li)
            self.prefills.append((out[0], 0, cl))
            return out

        def prefill_suffix(p, b, pkv, n, cl, li):
            out = suffix(p, b, pkv, n, cl, li)
            self.prefills.append((out[0], n, cl))
            return out

        def record_write_kv(idx, blocks):
            self.written += [int(b) for b in np.atleast_1d(idx)]
            return write_kv(idx, blocks)

        c._prefill_fn, c._psuffix_fn = prefill, prefill_suffix
        self.eng.write_kv = record_write_kv

    def pool(self):
        return np.array(self.eng.kv_rows_view(), copy=True)

    def admit(self, prompt, tick):
        """Admit one request; returns (skip, plen, cache_len)."""
        before, n0 = self.pool(), len(self.prefills)
        self.written.clear()
        req = Request(tick, np.asarray(prompt, np.int32), max_new=3)
        self.cluster._admit(0, self.cluster.replicas[0], deque([req]),
                            self.act, tick)
        after = self.pool()
        assert len(self.prefills) == n0 + 1
        cache, skip, cache_len = self.prefills[-1]
        plen = len(prompt)
        ref = _host_rows(self.stacks, self.eng.kv_token_row, after.dtype,
                         cache, skip, plen)
        pos = np.arange(skip, plen)
        page_row = self.act[-1].page_row
        slots = page_row[pos // BT].astype(np.int64) * BT + pos % BT
        np.testing.assert_array_equal(
            after[slots].view(np.uint16), ref.view(np.uint16))
        others = np.ones(len(after), bool)
        others[slots] = False
        for b in self.written:
            others[b * BT:(b + 1) * BT] = False
        np.testing.assert_array_equal(after[others].view(np.uint16),
                                      before[others].view(np.uint16))
        return skip, plen, cache_len


@pytest.mark.parametrize("backend", ["pallas", "numpy"])
@pytest.mark.parametrize("arch,mode", [("dense", "full"),
                                       ("dense", "suffix"),
                                       ("moe", "full"), ("moe", "suffix")])
def test_admission_rows_match_the_host_packing(arch, mode, backend):
    ad = _Admitter(arch, backend)
    if arch == "moe":
        # two stacks, each lane-padded: the packing has zeros to get right
        assert len(ad.stacks) == 2
        assert ad.eng.kv_token_row > sum(s.token_elems for s in ad.stacks)
    rng = np.random.default_rng(len(arch) + len(mode))
    prefix = rng.integers(1, ad.cfg.vocab, 2 * BT)
    seen = []
    # a prompt two pages and two tokens long: a full prefill whose bucket
    # holds two pad rows past the prompt
    seen.append(ad.admit(np.concatenate(
        [prefix, rng.integers(1, ad.cfg.vocab, 2)]), tick=0))
    assert seen[-1][0] == 0 and seen[-1][2] - seen[-1][1] == 2
    if mode == "suffix":
        # the same two pages and three tokens of its own: the prefix is
        # leased from the region, the suffix prefilled over it
        seen.append(ad.admit(np.concatenate(
            [prefix, rng.integers(1, ad.cfg.vocab, 3)]), tick=1))
        assert seen[-1][0] == 2 * BT
    else:
        # a prompt of one whole page and one short of a second
        seen.append(ad.admit(rng.integers(1, ad.cfg.vocab, 2 * BT - 1),
                             tick=1))
        assert seen[-1][0] == 0
    rep = ad.cluster.coherence_report()
    assert rep["admit_rows_on_device"] == sum(p - s for s, p, _ in seen)
    assert rep["admit_rows_skipped"] == sum(c - p for _, p, c in seen)
    assert rep["kv_tokens_appended"] == rep["admit_rows_on_device"]
