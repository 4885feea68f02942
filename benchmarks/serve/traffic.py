"""Open-loop request streams generated from a traffic mix file and a seed.

A mix file (``traffic/<mix>.json``) holds parameters only:

- ``arrivals``: ``process`` ``poisson`` or ``gamma`` (with ``cv``, the gaps'
  coefficient of variation) at ``rate_per_s``;
- ``initial_backlog``: requests already queued when the window opens;
- ``batch_always_full``: true where arrivals keep every batch slot taken
  (a backlog of at least the slots, then a rate above the knee), so the
  decode step only ever runs at the full batch;
- ``shared_prefix``: ``tenants`` system prompts of ``tokens`` tokens each,
  drawn from the file's own ``seed`` (fixed, so they are the same prompts in
  every run), or null;
- ``unique_prefix``: every request's prompt starts with a head of its own of
  ``tokens`` tokens, drawn from the run's seed, so no request shares a page
  with another (absent: nothing is drawn for it);
- ``prompt_tokens``: the length of each request's own part of the prompt,
  a log-normal (``median``, ``sigma``) clipped to [``min``, ``max``] and
  discretized to ``support`` points; ``output_tokens``: the same without
  ``support``;
- ``check``: how many finished requests, and how many served tokens, the
  correctness check samples.

Every seed gets the same schedule: the same arrival gaps, own-prompt
lengths, output lengths and tenants, in the same order, drawn once from a
fixed stream; the seed draws the token ids (and, in the harness, the
weights).  Near the knee the order of a bursty schedule alone moves time to
first token by several times from one permutation to the next, so a seed
that reordered it would change the work, not only the data.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# The gamma gaps and the schedule's order come from these fixed streams;
# stratified quantiles need no draw.
GAP_STREAM = 0
ORDER_STREAM = 1


@dataclasses.dataclass
class Req:
    idx: int
    due: float                # seconds after the window opens
    prompt: np.ndarray        # int32: own head, shared prefix, own part
    max_new: int
    tenant: int               # index of the shared prefix, -1 for none


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _lognormal_quantile(spec: dict, u: float) -> float:
    x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
    return min(max(x, spec["min"]), spec["max"])


def support_points(spec: dict) -> List[int]:
    """The distinct own-prompt lengths a mix can produce, ascending."""
    k = spec["support"]
    return sorted({int(round(_lognormal_quantile(spec, (j + 0.5) / k)))
                   for j in range(k)})


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's stratified quantiles; with a
    ``support``, each snapped to its stratum's support point."""
    u = (np.arange(n) + 0.5) / n
    if "support" in spec:
        pts = support_points(spec)
        # stratum of each quantile among the support's equal-mass strata
        qs = [_lognormal_quantile(spec, float(x)) for x in u]
        return np.asarray([min(pts, key=lambda p: abs(p - q)) for q in qs],
                          np.int64)
    return np.asarray([int(round(_lognormal_quantile(spec, float(x))))
                       for x in u], np.int64)


def arrival_gaps(arr: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` gaps whose sum is exactly ``seconds`` (so every seed offers
    the same load), with the process's shape."""
    if n == 0:
        return np.zeros(0)
    if arr["process"] == "poisson":
        u = (np.arange(n) + 0.5) / n
        g = -np.log1p(-u)
    elif arr["process"] == "gamma":
        k = 1.0 / arr["cv"] ** 2
        g = np.random.default_rng(GAP_STREAM).gamma(k, 1.0, n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return g * (seconds / g.sum())


def shared_prompts(mix: dict, vocab: int) -> List[np.ndarray]:
    sp = mix.get("shared_prefix")
    if not sp:
        return []
    rng = np.random.default_rng(sp["seed"])
    return [rng.integers(1, vocab, sp["tokens"]).astype(np.int32)
            for _ in range(sp["tenants"])]


def unique_head_tokens(mix: dict) -> int:
    """Tokens of the head each request has of its own (0: none)."""
    return int((mix.get("unique_prefix") or {}).get("tokens", 0))


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None) -> List[Req]:
    """The window's requests, ordered by due time.  ``rate`` overrides the
    mix's arrival rate (the knee sweep)."""
    arr = dict(mix["arrivals"])
    if rate is not None:
        arr["rate_per_s"] = rate
    backlog = int(mix.get("initial_backlog", 0))
    n_arr = int(round(arr["rate_per_s"] * seconds))
    n = backlog + n_arr
    order = np.random.default_rng(ORDER_STREAM)
    own = order.permutation(stratified_lengths(mix["prompt_tokens"], n))
    out = order.permutation(stratified_lengths(mix["output_tokens"], n))
    gaps = order.permutation(arrival_gaps(arr, n_arr, seconds))
    due = np.concatenate([np.zeros(backlog),
                          np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
                          if n_arr else np.zeros(0)])
    prefixes = shared_prompts(mix, vocab)
    tenants = (order.permutation(np.arange(n) % len(prefixes))
               if prefixes else np.full(n, -1))
    head = unique_head_tokens(mix)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        tok = rng.integers(1, vocab, int(own[i])).astype(np.int32)
        t = int(tenants[i])
        prompt = np.concatenate([prefixes[t], tok]) if t >= 0 else tok
        if head:
            prompt = np.concatenate(
                [rng.integers(1, vocab, head).astype(np.int32), prompt])
        reqs.append(Req(i, float(due[i]), prompt, int(out[i]), t))
    return reqs
