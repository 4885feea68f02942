"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Starts a Tardis-coherent replica cluster on the selected architecture and
serves synthetic batched requests.  By default the config is cut to smoke
scale for the CPU (``reduced``); ``--no-reduced`` serves the published
config at full width, which wants a chip (``chip_smoke.py`` at the
checkout's root drives tinyllama-1.1b that way, through
:func:`parse_args` / :func:`build` / :func:`serve`).

``--hosts K`` serves through K simulated hosts sharing one sharded lease
directory: the request stream is served in two phases (host 0 first, then
round-robin over the others) so the later hosts demonstrably reuse the
prefix pages host 0 prefilled -- the report grows per-host breakouts and
the directory's cross-host message ledger (``xhost_*``).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_arch, reduced
from ..core import CONSISTENCY_MODELS, CoherencePolicy
from ..models import init_params
from ..runtime import MultiHostServingCluster, Request, ServingCluster
from .compile_cache import enable_compile_cache


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-scale config for the CPU (default); "
                         "--no-reduced serves the published config")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--lease", type=int, default=8)
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="shared system-prompt tokens (prefix-KV reuse)")
    ap.add_argument("--prefix-block", type=int, default=8)
    ap.add_argument("--decode-pages", type=int, default=256,
                    help="allocator-region pool pages (admission bound)")
    ap.add_argument("--max-pages", type=int, default=32,
                    help="page-table length per request")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="continuous-batch slots per replica")
    ap.add_argument("--hosts", type=int, default=1,
                    help=">1: simulated hosts sharing a sharded lease "
                         "directory (cross-host prefix-KV migration)")
    ap.add_argument("--shards", type=int, default=0,
                    help="owner shards for --hosts mode (default: --hosts)")
    ap.add_argument("--roles", default="",
                    help="comma list of per-host roles (prefill|decode|"
                         "mixed), e.g. 'prefill,decode,decode'; implies "
                         "--hosts len(roles) and routes cold prefixes "
                         "through the prefill pods")
    ap.add_argument("--consistency", choices=CONSISTENCY_MODELS,
                    default="sc",
                    help="memory model for prefix-KV leases: tso/rc lets "
                         "decode serve tag-checked read-only blocks past "
                         "the lease end without a renewal message")
    ap.add_argument("--kv-lease", type=int, default=16,
                    help="base prefix-KV lease (logical ticks)")
    ap.add_argument("--lease-bounds", default="",
                    help="'min:max' bounds for the per-block lease "
                         "predictor; turns adaptive (Tardis 2.0) lease "
                         "prediction on")
    args = ap.parse_args(argv)
    args.roles = [r.strip() for r in args.roles.split(",") if r.strip()]
    if args.roles:
        if args.hosts > 1 and args.hosts != len(args.roles):
            raise SystemExit(
                f"--hosts {args.hosts} != {len(args.roles)} roles in --roles")
        args.hosts = len(args.roles)
    return args


def build(args: argparse.Namespace):
    """The config, a cluster holding freshly initialized weights (seed 0,
    float32), and the seeded request stream -- nothing served yet.
    Returns ``(cfg, cluster, requests)``."""
    if args.lease_bounds:
        lo, _, hi = args.lease_bounds.partition(":")
        policy = CoherencePolicy(
            consistency=args.consistency, lease=args.kv_lease,
            lease_min=int(lo), lease_max=int(hi), predictor=True)
    else:
        policy = CoherencePolicy(consistency=args.consistency,
                                 lease=args.kv_lease)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.family == "encdec":
        raise SystemExit("serve CLI drives decoder-only archs; whisper is "
                         "exercised via tests/dry-run (needs frame inputs)")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    kw = dict(n_replicas=args.replicas, lease=args.lease,
              prefix_block_tokens=args.prefix_block,
              policy=policy, cache_len=96,
              n_decode_pages=args.decode_pages,
              max_pages=args.max_pages,
              selfinc_period=4, max_batch=args.max_batch)
    rng = np.random.default_rng(0)
    system = rng.integers(1, cfg.vocab, args.prefix_len).astype(np.int32)
    reqs = [Request(i, np.concatenate(
                [system, rng.integers(1, cfg.vocab, rng.integers(4, 16))
                 .astype(np.int32)]), max_new=args.max_new)
            for i in range(args.requests)]
    roles = args.roles
    if args.hosts > 1:
        cluster = MultiHostServingCluster(
            cfg, lambda: params, n_hosts=args.hosts,
            n_shards=args.shards or None, roles=roles or None, **kw)
    else:
        cluster = ServingCluster(cfg, lambda: params, **kw)
    return cfg, cluster, reqs


def serve(args: argparse.Namespace, cluster, reqs):
    """Serve ``reqs`` the way the flags say; returns ``(requests,
    coherence report)``."""
    roles = args.roles
    if roles and any(r != "mixed" for r in roles):
        # disaggregated fleet: ONE routed run -- the admission router
        # forwards cold prefixes to the prefill pods, decode pods serve
        # the handed-back streams suffix-only (default decode affinity)
        return cluster.run(reqs)
    if args.hosts > 1:
        # phase 1: host 0 prefills + publishes the shared prefix; phase 2:
        # the other hosts serve the same system prompt suffix-only
        n0 = max(1, len(reqs) // args.hosts)
        cluster.run(reqs[:n0], affinity=[0] * n0)
        _, report = cluster.run(
            reqs[n0:],
            affinity=[1 + i % (args.hosts - 1)
                      for i in range(len(reqs) - n0)])
        return reqs, report
    return cluster.run(reqs)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    _, cluster, reqs = build(args)
    done, report = serve(args, cluster, reqs)
    roles = args.roles
    width = "reduced" if args.reduced else "full width"
    print(f"served {len(done)} requests on {args.replicas} replicas x "
          f"{args.hosts} host(s) ({args.arch} {width})")
    for k, v in report.items():
        print(f"  {k:28s} {v}")
    if report["prefix_prefill_tokens_skipped"]:
        print(f"paged-KV pool: prefill skipped "
              f"{report['prefix_prefill_tokens_skipped']} prompt tokens, "
              f"{report['prefix_flops_saved']/1e9:.2f} GFLOPs saved")
    if getattr(cluster, "paged", True):      # multi-host is always paged
        print(f"paged decode: {report['kv_tokens_appended']} token rows "
              f"through pool pages, peak {report['pool_page_peak']} pages "
              f"in use, {report['pool_pages_freed']} freed")
    if args.hosts > 1:
        print(f"sharded directory: {report['xhost_msgs']} cross-host msgs "
              f"({report['xhost_bytes']} bytes), "
              f"{report['xhost_migrations']} pages migrated, "
              f"{report['xhost_multicasts']} multicasts, "
              f"{report['xhost_invalidation_msgs']} invalidation msgs")
    if roles and any(r != "mixed" for r in roles):
        ticks = sum(report.get(f"host{h}_decode_ticks", 0)
                    for h, r in enumerate(roles) if r != "prefill")
        rmsgs = sum(report.get(f"host{h}_role_renewal_msgs", 0)
                    for h, r in enumerate(roles) if r != "prefill")
        per_tick = rmsgs / ticks if ticks else 0.0
        print(f"disaggregated: roles={','.join(roles)}, "
              f"{report['router_cold_forwards']} cold forwards, "
              f"{report['router_handoffs']} handoffs, "
              f"decode-pod renewal msgs/tick {per_tick:.3f}")


if __name__ == "__main__":
    main()
