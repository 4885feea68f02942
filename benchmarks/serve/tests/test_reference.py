"""The plain reference is the served model: same weights, same block."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import driver

CELL = bench.load_cell("mistral-nemo-12b.agent_shared")
DENSE = CELL.model               # the configuration's model package
weights, reference = DENSE.weights, DENSE.reference


@pytest.fixture(scope="module")
def small():
    return dataclasses.replace(CELL.sizes, n_layers=2, d_model=64, n_heads=4,
                               n_kv_heads=2, d_head=16, d_ff=96, vocab=300)


def test_one_layer_remade_bit_for_bit(small):
    seed = 2 ** 40 + 3                       # wider than 32 bits
    params = weights.make_params(seed, small)
    key = weights.root_key(seed)
    for li, lkey in enumerate(weights.layer_keys(key, small)):
        one = weights.layer_params(lkey, small)
        stacked = jax.tree.map(lambda t: t[li], params["layers"])
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    np.testing.assert_array_equal(
        np.asarray(weights.embed(key, small), np.float32),
        np.asarray(params["embed"], np.float32))


def test_reference_matches_the_program_forward_in_float32(small):
    """The program's own full forward with the same weights in float32
    agrees with the reference to float32 rounding at every position."""
    driver.import_program()
    from repro.models.transformer import forward, logits_fn
    seed = 11
    arch = driver.arch_config("small", DENSE.FAMILY, DENSE.arch_fields(small))
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          weights.make_params(seed, small))
    toks = np.random.default_rng(0).integers(1, small.vocab, 300)
    prog = np.asarray(logits_fn(arch, params, forward(
        arch, params, {"tokens": jnp.asarray(toks[None], jnp.int32)}))[0])
    ref = np.asarray(reference.logits_at(small, seed, [toks],
                                         np.arange(300), 512))
    scale = np.sqrt(np.mean(ref ** 2))
    assert np.max(np.abs(prog - ref)) / scale < 1e-4


def test_control_rounds_to_float8(small):
    """The float8 control's top tokens lie far below the reference's best,
    where the reference's own top tokens lie at 0."""
    toks = np.random.default_rng(1).integers(1, small.vocab, 100)
    ref = reference.logits_at(small, 5, [toks], np.arange(100), 512)
    top = reference.logits_at(small, 5, [toks], np.arange(100), 512,
                              mode="fp8")
    own, _ = bench._gap_stats(ref, jnp.argmax(ref, -1))
    ctl, _ = bench._gap_stats(ref, top)
    own, ctl = np.asarray(own), np.asarray(ctl)
    assert own.max() == 0
    assert ctl.max() > 1e-2
