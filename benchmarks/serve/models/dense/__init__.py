"""The dense decoder: pre-norm RMSNorm, GQA attention with rotary position,
SwiGLU MLP, untied head (the program's ``dense`` family).

The harness reaches a model only through this contract, which every
package under ``models/`` gives:

- ``FAMILY``: the program's ``ArchConfig.family``;
- ``sizes(conf)``: the model's sizes from the configuration file (any keys
  the model needs; ``vocab`` and ``n_layers`` always);
- ``arch_fields(sizes)``: keyword arguments of the program's
  ``ArchConfig``;
- ``cpu_sizes(sizes)``: reduced widths for tests on the CPU;
- ``make_params(seed, sizes)``: the served weights, on the device, from
  the seed;
- ``logits_at(sizes, seed, seqs, flat, length, mode)``: the float32
  reference (``mode="fp8"``: the float8 control's top tokens);
- the work counts, computed from shapes: ``decode_token_flops``,
  ``prefill_flops``, ``paged_attn_work`` (over every layer that runs the
  paged kernel), ``kv_row_bytes`` and ``token_row_elems``.
"""
from __future__ import annotations

import dataclasses

from .reference import logits_at
from .weights import make_params
from .work import (decode_token_flops, kv_row_bytes, paged_attn_work,
                   prefill_flops, token_row_elems)

FAMILY = "dense"

__all__ = ["FAMILY", "Sizes", "sizes", "arch_fields", "cpu_sizes",
           "make_params", "logits_at", "decode_token_flops",
           "prefill_flops", "paged_attn_work", "kv_row_bytes",
           "token_row_elems"]


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The decoder's sizes as the configuration file states them."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    def head_dim(self) -> int:
        return self.d_head


def sizes(conf: dict) -> Sizes:
    """``arch_keys`` maps each size to the configuration's own key."""
    return Sizes(**{k: conf[v] for k, v in conf["arch_keys"].items()})


def arch_fields(s: Sizes) -> dict:
    return dataclasses.asdict(s)


def cpu_sizes(s: Sizes) -> Sizes:
    return dataclasses.replace(
        s, n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2 if s.n_kv_heads > 2 else 1, d_head=16, d_ff=128,
        vocab=512)
