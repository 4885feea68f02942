"""The check that decides ``correct``: its control, and the faults a
serving cell can have, each driven through a whole run on the CPU (reduced
widths, the cell's own pool and traffic) with the look for a chip skipped.

- sound: the program as it is passes;
- control: the tokens the float8 control puts first, judged in place of the
  served ones through the same limit, fail, and read at least three times
  the served tokens' widest gap on the same sample;
- a decode step that returns its KV pool unchanged (no append) fails;
- every step that writes the KV pool returning it unchanged (no append at
  admission or decode) fails;
- a token altered where it is produced fails.

The unshared cell is not held to the decode-only fault: behind a
4096-token head of its own, a request's missing output rows shift its
logits too little for the check to see at reduced widths (in a 60-s
window its served tokens read 0.0072 with the fault, 0.0062 without).

Half of a batch left out and a missing exchange between chips are training
and multi-chip faults: no serving cell here can have them.
"""
import contextlib

import pytest

import bench
import cpu_cell
import driver

# the window each cell needs on the CPU to finish requests for the check
SECONDS = {"glm4-9b.reasoning_overload": 45.0,
           "mistral-nemo-12b.unshared_control": 120.0}
SEED = 2 ** 34 + 17
# At these reduced widths (2 layers) the float8 control moves the logits
# less than at the cell's own size: on SEED the served tokens read about
# 0.02 (reasoning) and under 0.01 (unshared), the control 0.1 to 0.3,
# under the cells' limits of 0.32 and 0.2, set from full-width chip
# readings.  The reduced cells are held to a limit set between their own
# two readings.
REDUCED_LIMIT = 0.1


@contextlib.contextmanager
def fault(name):
    driver.import_program()
    from repro.kernels.tardis_lease import kernel, ops
    if name == "state_unchanged":
        saved = kernel.scatter_rows
        kernel.scatter_rows = lambda pool, idx, rows, **kw: pool
        try:
            yield
        finally:
            kernel.scatter_rows = saved
    elif name == "pool_unchanged":
        saved = kernel.scatter_rows, ops.append_rows
        kernel.scatter_rows = lambda pool, idx, rows, **kw: pool
        ops.append_rows = lambda pool, idx, rows, **kw: pool
        try:
            yield
        finally:
            kernel.scatter_rows, ops.append_rows = saved
    elif name == "token_altered":
        saved = driver.tick

        def tick(cluster, queues, n):
            saved(cluster, queues, n)
            for st in driver.active(cluster):
                toks = driver.stream_tokens(st)
                toks[-1] = (toks[-1] + 1) % cluster.cfg.vocab
        driver.tick = tick
        try:
            yield
        finally:
            driver.tick = saved
    else:
        yield


def _check_sees(name, workload):
    cell = cpu_cell.reduced_cell(workload)
    cell.limit = {"widest_gap": {"limit": REDUCED_LIMIT}}
    with cpu_cell.plain_row_kernels(), fault(name):
        res = bench.run_cell(cell, SEED, SECONDS[workload], trace=False,
                             control=(name == "control"))
    chk = res["check"]
    assert chk["widest_gap"]["value"] is not None
    if name == "sound":
        assert res["correct"], chk
        return
    assert not res["correct"], chk
    assert res["failed"] >= 1
    if name == "control":
        served = res["control"]["served_widest_gap"]
        assert chk["widest_gap"]["value"] >= 3 * served, (chk, served)


@pytest.mark.parametrize("name", ["sound", "control", "state_unchanged",
                                  "token_altered"])
def test_check_sees_each_fault(name):
    _check_sees(name, "glm4-9b.reasoning_overload")


@pytest.mark.parametrize("name", ["sound", "control", "pool_unchanged",
                                  "token_altered"])
def test_check_sees_each_fault_unshared(name):
    """The same, where every prompt starts with a 4096-token head of its
    own: the reference covers the head too."""
    _check_sees(name, "mistral-nemo-12b.unshared_control")
