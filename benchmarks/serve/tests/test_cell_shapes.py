"""Each cell's traffic, on the CPU at reduced widths but with the cell's own
page size, region slots, batch, length support and arrivals, compiles
nothing once set-up is over: every decode, prefill, suffix-prefill, append
and lease shape the window reaches was in the warm set.

The share of prompt tokens read from leased pages is an exact count and
does not depend on the device; it is printed for the shared cell, and is
0 where every request's head is its own.
"""
import pytest

import bench
import cpu_cell

CELLS = ["mistral-nemo-12b.agent_shared", "glm4-9b.reasoning_overload",
         "mistral-nemo-12b.unshared_control"]


@pytest.mark.parametrize("workload", CELLS)
def test_window_compiles_nothing(workload):
    cell = cpu_cell.reduced_cell(workload)
    with cpu_cell.plain_row_kernels():
        res = bench.run_cell(cell, 2 ** 33 + 101, 20.0, trace=False)
    win = res["window"]
    assert win["backend_compiles"] == 0, win
    if cell.mix.get("shared_prefix"):
        share = 100.0 * win["prefill_tokens_skipped"] / max(
            1, win["prompt_tokens_admitted"])
        print(f"{workload}: prefix_token_share {share:.3f}% "
              f"({win['prefill_tokens_skipped']} of "
              f"{win['prompt_tokens_admitted']} prompt tokens)")
        assert share > 0
    if cell.mix.get("unique_prefix"):
        assert win["prompt_tokens_admitted"] > 0, win
        assert win["prefill_tokens_skipped"] == 0, win
