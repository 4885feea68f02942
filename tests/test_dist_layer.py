"""Unit tests for the repro.dist layer beyond what the substrate suite pins:
batch/cache placement rules, sharding tree structure, and the ambient-mesh
behaviour of the activation annotations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch, reduced
from repro.dist import annotate
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import cache_specs
from repro.models import abstract_params
from repro.optim import adamw


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


class TestBatchSpec:
    def test_batch_dim_over_dp(self):
        assert shd.batch_spec(MULTI, (256, 4096)) == P(("pod", "data"), None)

    def test_indivisible_batch_replicates(self):
        assert shd.batch_spec(MULTI, (1, 64)) == P(None, None)

    def test_uneven_batch_drops_pod(self):
        assert shd.batch_spec(MULTI, (16, 64)) == P("data", None)

    def test_scalar_leaf_replicates(self):
        assert shd.batch_spec(MULTI, ()) == P()

    def test_activation_spec_sequence_parallel(self):
        # residual stream (B, S, D): batch over DP, sequence over the
        # otherwise-idle model axis -- the long-context activation fix
        assert shd.activation_spec(MULTI, (16, 500000, 1024)) == \
            P("data", "model", None)
        assert shd.activation_spec(MULTI, (256, 4096, 1024)) == \
            P(("pod", "data"), "model", None)

    def test_activation_spec_guards(self):
        # 2-D activations never sequence-shard; indivisible seq replicates
        assert shd.activation_spec(MULTI, (256, 4096)) == \
            P(("pod", "data"), None)
        assert shd.activation_spec(MULTI, (16, 4097, 1024)) == \
            P("data", None, None)
        assert shd.activation_spec(MULTI, ()) == P()

    def test_shardings_tree_structure(self):
        mesh = make_host_mesh(data=1, model=1)
        batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
        out = shd.batch_shardings(mesh, batch)
        assert set(out) == {"tokens", "labels"}
        for ns in out.values():
            assert isinstance(ns, NamedSharding)
            assert len(ns.spec) == 2


class TestCacheSpec:
    def test_kv_cache_rule(self):
        cfg = get_arch("tinyllama-1.1b")
        cache = cache_specs(cfg, 128, 32768)
        sk = shd.cache_spec(MULTI, "k", cache["k"].shape)
        # (L, B, T, H, Dh): batch over DP, kv heads over model if divisible
        assert sk[1] == ("pod", "data")
        hk = cache["k"].shape[3]
        assert sk[3] == ("model" if hk % 16 == 0 else None)
        assert sk[0] is None and sk[2] is None

    def test_ssm_cache_rule(self):
        cfg = get_arch("mamba2-130m")
        cache = cache_specs(cfg, 128, 32768)
        st = shd.cache_spec(MULTI, "state", cache["state"].shape)
        assert st[1] == ("pod", "data")          # (L, B, H, P, N)
        h = cache["state"].shape[2]
        assert st[2] == ("model" if h % 16 == 0 else None)

    def test_shardings_tree_matches_for_every_family(self):
        mesh = make_host_mesh(data=1, model=1)
        for arch in ("tinyllama-1.1b", "kimi-k2-1t-a32b", "zamba2-2.7b",
                     "whisper-large-v3"):
            cfg = get_arch(arch)
            cache = cache_specs(cfg, 128, 1024,
                                1024 if cfg.family == "encdec" else 0)
            out = shd.cache_shardings(mesh, cache)
            assert jax.tree.structure(out) == jax.tree.structure(cache)
            for ns in jax.tree.leaves(out):
                assert isinstance(ns, NamedSharding)


class TestOptShardings:
    def test_moments_mirror_params_step_replicates(self):
        mesh = make_host_mesh(data=1, model=1)
        cfg = reduced(get_arch("tinyllama-1.1b"))
        params = abstract_params(cfg, jnp.float32)
        pshard = shd.param_shardings(mesh, params)
        opt = jax.eval_shape(adamw.init, params)
        out = shd.opt_shardings(mesh, opt, pshard)
        assert jax.tree.structure(out) == jax.tree.structure(opt)
        assert out["m"] is pshard and out["v"] is pshard
        assert out["step"].spec == P()


class TestAnnotate:
    def test_noop_without_mesh(self):
        assert annotate.ambient_mesh() is None
        x = jnp.ones((4, 8, 16))
        assert annotate.batch_activations(x) is x
        assert annotate.replicate(x) is x

    def test_noop_under_jit_without_mesh(self):
        x = jnp.ones((4, 8))
        y = jax.jit(annotate.batch_activations)(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_constrains_under_ambient_mesh(self):
        mesh = make_host_mesh(data=1, model=1)
        x = jnp.ones((4, 8, 16))
        with jax.set_mesh(mesh):
            assert annotate.ambient_mesh() is not None
            y = jax.jit(annotate.batch_activations)(x)
            z = jax.jit(annotate.replicate)(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(z), np.asarray(x))

    def test_value_preserved_through_grad(self):
        mesh = make_host_mesh(data=1, model=1)

        def f(x):
            return jnp.sum(annotate.batch_activations(x) ** 2)
        x = jnp.arange(8.0).reshape(2, 4)
        with jax.set_mesh(mesh):
            g = jax.grad(f)(x)
        np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x))


class TestParamShardingsEndToEnd:
    def test_every_leaf_gets_named_sharding(self):
        cfg = get_arch("kimi-k2-1t-a32b")
        params = abstract_params(cfg)
        mesh = make_host_mesh(data=1, model=1)
        pshard = shd.param_shardings(mesh, params)
        assert jax.tree.structure(pshard) == jax.tree.structure(params)
        for leaf, ns in zip(jax.tree.leaves(params), jax.tree.leaves(pshard)):
            assert isinstance(ns, NamedSharding)
            assert len(ns.spec) == len(leaf.shape)

    def test_moe_expert_placement_spec(self):
        # full kimi config on the multi-pod mesh: experts -> model (EP),
        # d_model -> ('pod','data') FSDP, layer axis replicated.
        cfg = get_arch("kimi-k2-1t-a32b")
        e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
        n_moe = cfg.n_layers - cfg.first_dense_layers
        up = shd.param_spec(MULTI, ("layers", "moe", "w_up"), (n_moe, e, d, f))
        assert up[0] is None and up[1] == "model"
        down = shd.param_spec(MULTI, ("layers", "moe", "w_down"),
                              (n_moe, e, f, d))
        assert down[0] is None and down[1] == "model"
        # d_model dim carries the FSDP axes on both layouts
        assert up[2] == shd._dp_axes(MULTI, d)
        assert down[3] == shd._dp_axes(MULTI, d)
