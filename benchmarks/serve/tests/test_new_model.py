"""A model is added as files only: the fixture under ``data/new_model/``
lays out what a new model brings (``models/<model>/``,
``configs/<config>.json``, ``traffic/<mix>.json``, ``limits/<workload>.json``
and its own ``BENCHMARK.json`` entries), no file outside it names the model,
and its cell runs through ``load_cell``, the window and the check to
``correct: true`` on the CPU."""
import json
import os

import bench
import cpu_cell

TESTS = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(TESTS, "data", "new_model")
HARNESS = os.path.dirname(TESTS)


def _fixture_cell():
    with open(os.path.join(FIXTURE, "BENCHMARK.json")) as f:
        (w,) = json.load(f)["workloads"]
    return bench.load_cell(w["name"], FIXTURE)


def test_no_file_outside_the_fixture_names_the_model():
    cell = _fixture_cell()
    name = cell.conf["model"]
    assert cell.model.__name__ == f"models.{name}"
    assert os.path.dirname(cell.model.__file__) == os.path.join(
        FIXTURE, "serve", "models", name)
    seen = [os.path.join(bench.ROOT, "BENCHMARK.json")]
    for dirpath, dirs, files in os.walk(HARNESS):
        dirs[:] = [d for d in dirs if d != "__pycache__"
                   and os.path.join(dirpath, d) != FIXTURE]
        seen += [os.path.join(dirpath, f) for f in files]
    assert len(seen) > 40
    for path in seen:
        with open(path, "rb") as f:
            assert name.encode() not in f.read(), path


def test_fixture_cell_runs_correct():
    cell = _fixture_cell()
    with cpu_cell.plain_row_kernels():
        res = bench.run_cell(cell, 2 ** 35 + 11, 8.0, trace=False)
    assert res["window"]["backend_compiles"] == 0, res["window"]
    assert res["check"]["widest_gap"]["value"] is not None
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}
