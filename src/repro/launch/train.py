"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs config -> params -> step -> fault-tolerant loop on one device.  By
default the config is cut to smoke scale for the CPU (``reduced``);
``--no-reduced`` trains the published config, which wants a chip.
"""
import argparse

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_arch, reduced
from ..models import init_params
from ..runtime import TrainConfig, train
from .compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-scale config for the CPU (default); "
                         "--no-reduced trains the published config")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = init_params(cfg, jax.random.PRNGKey(args.seed), jnp.float32)
    n = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={args.arch} family={cfg.family} params={n/1e6:.1f}M "
          f"devices={jax.device_count()}")

    tc = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                     batch=args.batch, seq=args.seq, seed=args.seed,
                     grad_compression=args.grad_compression,
                     n_micro=args.n_micro)
    out = train(cfg, params, tc,
                on_metrics=lambda s, m: print(
                    f"step {s:5d} loss {m['loss']:.4f} "
                    f"lr {m['lr']:.2e} {m['step_s']*1e3:.0f}ms"))
    print(f"final loss {out['losses'][-1]:.4f} "
          f"(restarts={out['restarts']}, stragglers={out['stragglers']})")


if __name__ == "__main__":
    main()
