"""The serving loop's spans and admission counters.

Each span ``<name>`` adds its host time to ``span_<name>_ns`` and one to
``span_<name>_n`` of the cluster's flat counters, and puts ``serve.<name>``
on a profiler trace's host plane.  Checked here on a tiny paged cluster:
the counts agree with the scheduler's own (decode ticks, admission groups,
requests served), nested spans never outlast their parent, a multi-host
report sums its hosts' keys, and a CPU profiler trace holds the spans.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.policy import CoherencePolicy
from repro.models import init_params
from repro.runtime import MultiHostServingCluster, Request, ServingCluster
from repro.runtime.serve_loop import SPAN_NAMES
from repro.runtime.spans import Spans

KW = dict(n_replicas=1, prefix_block_tokens=4,
          policy=CoherencePolicy(lease=16), cache_len=96, selfinc_period=4,
          n_decode_pages=64, max_pages=16, max_batch=2)
ADMIT_CHILDREN = ("lease_wave", "pool_read", "kv_append", "prefill_wait")
DECODE_CHILDREN = ("renew", "decode_wait")


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_arch("tinyllama-1.1b"), n_layers=2, d_model=64,
                   vocab=128)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _requests(cfg, n, seed=0):
    """Turns over one shared 12-token prompt, own tails and outputs of
    varied length, so admissions come in groups of one and two."""
    rng = np.random.default_rng(seed)
    system = rng.integers(1, cfg.vocab, 12).astype(np.int32)
    return [Request(i, np.concatenate(
        [system, rng.integers(1, cfg.vocab, 3 + i).astype(np.int32)]),
        max_new=2 + i % 3) for i in range(n)]


def _span_keys(rep):
    return [k for k in rep if k.startswith("span_")]


def _served(cfg, params, reqs):
    """Run the scheduler tick by tick; returns the cluster, the report
    before and after, and the admission groups seen from outside (ticks
    in which the queue shrank)."""
    cluster = ServingCluster(cfg, lambda: params, **KW)
    before = cluster.coherence_report()
    queues = cluster._mk_queues(reqs)
    groups, tick = 0, 0
    while cluster._busy(queues):
        queued = len(queues[0])
        cluster._paged_tick(queues, tick)
        groups += len(queues[0]) < queued
        tick += 1
    return cluster, before, cluster.coherence_report(), groups


def test_span_counts_match_the_scheduler(cfg, params):
    reqs = _requests(cfg, 5)
    # one request waited a second in some queue before this one
    reqs[-1].t_queued -= 10 ** 9
    cluster, before, rep, groups = _served(cfg, params, reqs)
    assert all(r.done for r in reqs)
    assert {f"span_{n}_{u}" for n in SPAN_NAMES for u in ("ns", "n")} \
        == set(_span_keys(rep))
    assert all(before[k] == 0 for k in _span_keys(before))
    decode_ticks = rep["decode_ticks"] - before["decode_ticks"]
    assert decode_ticks > 0
    assert rep["span_decode_n"] == decode_ticks
    assert rep["span_renew_n"] == rep["span_decode_wait_n"] == decode_ticks
    assert 1 < groups < len(reqs)
    assert rep["span_admit_n"] == rep["span_lease_wave_n"] == groups
    # every admission appends its suffix KV and waits on its prefill once;
    # the KV takes no trip through the host, so no span is kept for one
    assert set(SPAN_NAMES) == {"admit", *ADMIT_CHILDREN, "decode",
                               *DECODE_CHILDREN, "rebase"}
    for name in ("kv_append", "prefill_wait"):
        assert rep[f"span_{name}_n"] == len(reqs)
    assert 0 < rep["span_pool_read_n"] <= len(reqs)
    assert rep["requests_admitted"] == len(reqs)
    assert rep["queue_wait_ns"] >= 10 ** 9


def test_nested_spans_fit_inside_their_parent(cfg, params):
    _, _, rep, _ = _served(cfg, params, _requests(cfg, 5, seed=1))
    assert 0 < rep["span_decode_wait_ns"] <= rep["span_decode_ns"]
    assert 0 < rep["span_prefill_wait_ns"] <= rep["span_admit_ns"]
    assert sum(rep[f"span_{n}_ns"] for n in ADMIT_CHILDREN) \
        <= rep["span_admit_ns"]
    assert sum(rep[f"span_{n}_ns"] for n in DECODE_CHILDREN) \
        <= rep["span_decode_ns"]


def test_admission_waits_on_the_device_once_after_its_append(
        cfg, params, monkeypatch):
    """Inside an admission the KV append reads no device array back to the
    host, and the one wait on the prefill (the read of its last logits)
    comes after the append has been dispatched."""
    cluster = ServingCluster(cfg, lambda: params, **KW)
    spans, order, reads = cluster._span, [], []
    array_t = type(jnp.zeros(()))
    to_host = array_t.__array__

    def counted(self, *a, **k):
        if order and order[-1] == "kv_append":
            reads.append(self.shape)
        return to_host(self, *a, **k)

    monkeypatch.setattr(array_t, "__array__", counted)

    class Recorded:
        def __init__(self, name):
            self._span = spans(name)
            self._name = name

        def __enter__(self):
            order.append(self._name)
            return self._span.__enter__()

        def __exit__(self, *exc):
            order.append(f"/{self._name}")
            return self._span.__exit__(*exc)

    cluster._span = Recorded
    reqs = _requests(cfg, 4, seed=2)
    cluster.run(reqs)
    assert all(r.done for r in reqs)
    assert reads == []
    inner = [n for n in order if n in ("kv_append", "prefill_wait")]
    assert inner == ["kv_append", "prefill_wait"] * len(reqs)


@pytest.mark.parametrize("roles", [["mixed", "mixed"], ["prefill", "decode"]])
def test_multi_host_report_sums_span_keys(cfg, params, roles):
    mh = MultiHostServingCluster(cfg, lambda: params, n_hosts=2,
                                 roles=roles, **KW)
    reqs = _requests(cfg, 4)
    _, rep = mh.run(reqs)
    hosts = [h.coherence_report() for h in mh.hosts]
    keys = _span_keys(rep) + ["requests_admitted", "queue_wait_ns"]
    assert len(keys) == 2 * len(SPAN_NAMES) + 2
    for k in keys:
        assert rep[k] == sum(h[k] for h in hosts), k
    # a prefill pod's shadow admissions are spans, never requests
    assert rep["requests_admitted"] == len(reqs)
    if "prefill" in roles:
        assert hosts[0]["span_admit_n"] > 0
        assert hosts[0]["requests_admitted"] == 0


def test_undeclared_span_is_refused():
    counters = {}
    spans = Spans(counters, ("a",))
    with spans("a"):
        pass
    assert counters["span_a_n"] == 1 and counters["span_a_ns"] >= 0
    with pytest.raises(KeyError, match="undeclared"):
        spans("b")


def test_spans_reach_the_profiler_host_plane(cfg, params, tmp_path):
    from jax.profiler import ProfileData
    cluster = ServingCluster(cfg, lambda: params, **KW)
    with jax.profiler.trace(str(tmp_path)):
        cluster.run(_requests(cfg, 3))
    paths = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    assert paths
    names = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"serve.admit", "serve.lease_wave", "serve.decode",
            "serve.decode_wait"} <= names
