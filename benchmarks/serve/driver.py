"""The one adapter between the harness and the program under test.

Every program name the harness touches is here, private ones included: the
program has no public per-tick entry that takes arrival times, so the open
loop calls ``ServingCluster._paged_tick`` itself and reads the live streams
out of ``_active``.  A program change that renames these is repointed here
and nowhere else.
"""
from __future__ import annotations

import gc
import os
import sys
from collections import deque
from typing import Dict, List

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(CHECKOUT, "src")


def import_program() -> None:
    """Put the checkout's ``src`` on the path; fail when it is missing."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"bench: no program under {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def enable_compile_cache() -> str:
    from repro.launch.compile_cache import enable_compile_cache as enable
    return enable()


def arch_config(name: str, family: str, fields: Dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(name=name, family=family, **fields)


def build_cluster(arch, params, serving: Dict):
    """One replica over the configuration's page pool."""
    from repro.core.policy import CoherencePolicy
    from repro.runtime import ServingCluster
    policy = CoherencePolicy(consistency=serving["consistency"],
                             lease=serving["kv_lease"])
    return ServingCluster(
        arch, lambda: params, n_replicas=1, policy=policy,
        n_prefix_blocks=serving["n_prefix_blocks"],
        prefix_block_tokens=serving["page_tokens"],
        n_decode_pages=serving["n_decode_pages"],
        max_pages=serving["max_pages"], max_batch=serving["max_batch"])


def new_queues() -> List[deque]:
    return [deque()]


def submit(queues: List[deque], rid: int, prompt, max_new: int):
    """Queue a request on the one replica; returns the program's object."""
    from repro.runtime import Request
    req = Request(rid, prompt, max_new=max_new)
    queues[0].append(req)
    return req


def tick(cluster, queues: List[deque], n: int) -> None:
    """One scheduler tick: admissions, then one decode step.  It ends in
    the host's read of the step's argmax, so the host clock after it is a
    completion time."""
    cluster._paged_tick(queues, n)


def busy(cluster, queues: List[deque]) -> bool:
    return bool(queues[0]) or bool(cluster._active[0])


def active(cluster) -> List:
    """The live streams of the one replica."""
    return list(cluster._active[0])


def stream_request(stream):
    return stream.req


def stream_tokens(stream) -> List[int]:
    """Tokens the stream has emitted so far (its first comes from
    prefill)."""
    return stream.emitted


def stream_length(stream) -> int:
    """Tokens of the stream held in pool pages."""
    return stream.length


def stream_skip(cluster, stream) -> int:
    """Prompt tokens the stream's admission read from leased pages."""
    return (len(stream.shared_bids) + len(stream.reloc_pages)) \
        * cluster.prefix_block_tokens


def request_done(req) -> bool:
    return bool(req.done)


def request_output(req) -> List[int]:
    return [] if req.output is None else [int(t) for t in req.output]


def counters(cluster) -> Dict[str, int]:
    """The program's exact counters (``coherence_report``), numbers only."""
    rep = cluster.coherence_report()
    return {k: v for k, v in rep.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def pages_in_use(cluster) -> int:
    """Decode pages taken off the pool's free list: a stream holds its
    whole need (prompt past its leased pages, and its output) from
    admission."""
    return cluster.n_decode_pages - cluster.prefix_engine.free_page_count()


def region_slots_filled(cluster) -> int:
    """Prefix-region slots that hold a page's content."""
    return int((cluster._tags[:cluster.n_prefix_blocks] != -1).sum())


def page_bytes(cluster) -> int:
    """Bytes of one pool page: its token rows, every layer's K and V."""
    pool = cluster.prefix_engine._kv_pool
    return (pool.shape[1] * pool.dtype.itemsize
            * cluster.prefix_block_tokens)


def warm_lease_shapes(cluster, max_groups: int, max_blocks: int) -> None:
    """Run the lease engine's batched read at every (distinct requester
    rows, union size) it can meet, and one write, on a scratch engine of
    the same size: the compiled programs are shared, the tables are not."""
    import numpy as np
    from repro.core.lease_engine import LeaseEngine
    eng = LeaseEngine(cluster.prefix_engine.n_blocks, policy=cluster.policy)
    for u in range(1, max_blocks + 1):
        for g in range(1, min(max_groups, u) + 1):
            eng.read_many([list(range(u - j)) for j in range(g)], 1,
                          req_wts={b: 0 for b in range(u)})
    eng.write_many([list(range(max_blocks))], 2)
    np.asarray(eng.rts)


def release(cluster) -> None:
    """Drop the program's device state (weights, pool) before the
    reference runs beside it."""
    cluster.prefix_engine._kv_pool = None
    cluster.store._val.clear()
    cluster.publisher = None
    for rep in cluster.replicas:
        rep.reader = None
    gc.collect()
