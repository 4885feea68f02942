"""The dense model reads what the harness read before it moved behind the
model interface: the weights, the reference's logits and the float8
control's top tokens at CPU sizes, and the existing mixes' schedules and
token ids, each pinned from the harness as it was before (the weights and
schedules by SHA-256 of their bytes, the logits in ``data/dense_pin.npz``).
"""
import hashlib
import os

import jax
import numpy as np
import pytest

import bench
import traffic

PIN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "dense_pin.npz")
SEED = 2 ** 40 + 3
PARAMS_SHA = "030a15a6b2088a175161f4d472d72750ec95e57797737c99435c0889ebf70973"
SCHEDULE_SHA = {
    "mistral-nemo-12b.agent_shared":
    (41, "8102faeb16f8bf7895c3d3f2f7a5df51b56d969f9102e1378adc71678cdaa595"),
    "glm4-9b.reasoning_overload":
    (93, "c561bafa353f94c096b42a84ff91c36423e26daa395e2fedcb10b27d26d7338b"),
}


@pytest.fixture(scope="module")
def cell():
    c = bench.load_cell("mistral-nemo-12b.agent_shared")
    c.sizes = c.model.cpu_sizes(c.sizes)
    return c


def _pinned_rows():
    rng = np.random.default_rng(3)
    seqs = [rng.integers(1, 512, 700).astype(np.int32),
            rng.integers(1, 512, 300).astype(np.int32)]
    flat = np.concatenate([np.arange(690, 700), 1024 + np.arange(290, 300)])
    return seqs, flat


def test_weights_checksum(cell):
    params = cell.model.make_params(SEED, cell.sizes)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PARAMS_SHA


def test_reference_logits_and_control(cell):
    pin = np.load(PIN)
    seqs, flat = _pinned_rows()
    ref = np.asarray(cell.model.logits_at(cell.sizes, SEED, seqs, flat, 1024,
                                          "f32"))
    # the same program on the same CPU gives the same bits; the tolerance
    # is float32 rounding, for a CPU whose XLA orders sums otherwise
    np.testing.assert_allclose(ref, pin["logits"], rtol=1e-6, atol=1e-6)
    top = np.asarray(cell.model.logits_at(cell.sizes, SEED, seqs, flat, 1024,
                                          "fp8"))
    np.testing.assert_array_equal(top, pin["fp8_top"])


@pytest.mark.parametrize("workload", sorted(SCHEDULE_SHA))
def test_schedule_and_token_ids(workload):
    c = bench.load_cell(workload)
    reqs = traffic.generate(c.mix, 2 ** 33 + 5, 51.0, c.sizes.vocab)
    h = hashlib.sha256()
    for r in reqs:
        h.update(np.float64(r.due).tobytes())
        h.update(r.prompt.tobytes())
        h.update(np.int64(r.max_new).tobytes())
        h.update(np.int64(r.tenant).tobytes())
    assert (len(reqs), h.hexdigest()) == SCHEDULE_SHA[workload]
