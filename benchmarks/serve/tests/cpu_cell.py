"""Running a cell on the CPU for tests: reduced widths, the cell's own page
size, region, batch and traffic, and the pool's row kernels swapped for
plain ``jax.numpy`` scatters and gathers.

In interpret mode the row kernels copy the whole pool per row, minutes per
admission; the swap keeps every shape (each distinct shape still compiles
once) and every result bit, and the lease-table kernels still run in
interpret mode.
"""
from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import bench  # noqa: E402
import driver  # noqa: E402


def reduced_cell(workload: str):
    cell = bench.load_cell(workload)
    cell.sizes = cell.model.cpu_sizes(cell.sizes)
    return cell


@contextlib.contextmanager
def plain_row_kernels():
    driver.import_program()
    import jax
    import jax.numpy as jnp
    from repro.kernels.tardis_lease import kernel, ops

    def scatter_rows(pool, idx, rows, *, col_lo=0, interpret=False):
        w = rows.shape[1]
        return pool.at[idx, col_lo:col_lo + w].set(rows.astype(pool.dtype))

    @jax.jit
    def _append(pool, idx, rows):
        return pool.at[idx, :rows.shape[1]].set(rows.astype(pool.dtype))

    def append_rows(pool, idx, rows, col_lo=0, width=None, interpret=False):
        assert col_lo == 0
        return _append(pool, jnp.asarray(idx), rows)

    @jax.jit
    def _gather(pool, idx):
        return pool[idx]

    def gather_blocks(pool, idx, col_lo=0, width=None, interpret=False):
        assert col_lo == 0 and width in (None, pool.shape[1])
        return _gather(pool, idx)

    saved = (kernel.scatter_rows, ops.append_rows, ops.gather_blocks)
    kernel.scatter_rows = scatter_rows
    ops.append_rows, ops.gather_blocks = append_rows, gather_blocks
    try:
        yield
    finally:
        kernel.scatter_rows, ops.append_rows, ops.gather_blocks = saved
