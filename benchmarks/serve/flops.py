"""What no model changes in the rooflines: the chip's least time for a
piece of work, and the bytes of a KV append given the model's row size.
Each model's own counts are in its package (``models/<model>/``)."""
from __future__ import annotations


def append_bytes(row_bytes: int, rows: int) -> int:
    """Appending ``rows`` token rows of ``row_bytes`` each (the model's
    ``kv_row_bytes``): each read once and written once."""
    return 2 * rows * row_bytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
