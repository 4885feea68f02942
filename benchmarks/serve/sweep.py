#!/usr/bin/env python3
"""Find a cell's knee: one set-up, then one window per offered rate.

    python3 benchmarks/serve/sweep.py --workload <name> --seed <n> \\
        --seconds <s> --rates 2,3,4,5

Each window runs the cell's traffic at the given arrival rate; between
windows the server drains.  A rate is sustained when the requests still
waiting for admission at the close are few and time to first token does
not climb through the window.  The knee is measured once, when a cell is
defined, and written into the mix file as a number; the benchmark's own
runs never search for a rate.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import bench  # noqa: E402
import driver  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    bench.add_libtpu_flags(cell)
    driver.import_program()
    import jax
    if jax.devices()[0].platform != "tpu":
        bench.log("sweep: JAX found no TPU")
        return 3
    comp = bench.Compiles(jax)
    driver.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    s = cell.sizes
    cluster = driver.build_cluster(
        driver.arch_config(cell.config_name, cell.model.FAMILY,
                           cell.model.arch_fields(s)),
        cell.model.make_params(args.seed, s), cell.serving)
    bench.warm(cluster, cell, comp)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = traffic.generate(cell.mix, args.seed + i, args.seconds,
                                s.vocab, rate=rate)
        rec = bench.window(cluster, cell, reqs, args.seconds, comp, False)
        e2e = bench.end_to_end(rec)
        close = rec["close"]
        waiting = sum(1 for r in reqs
                      if not rec["times"].get(r.idx)
                      and rec["due"][r.idx] < close)
        half = rec["open"] + args.seconds / 2
        early = [rec["times"][k][0] - rec["due"][k] for k in rec["due"]
                 if rec["times"].get(k) and rec["due"][k] < half]
        late = [rec["times"][k][0] - rec["due"][k] for k in rec["due"]
                if rec["times"].get(k) and rec["due"][k] >= half]
        row = {"rate": rate, "requests": len(reqs), "waiting_at_close":
               waiting, "ttft_p50_first_half_ms":
               1e3 * (stats.percentile(early, 50) or 0),
               "ttft_p50_second_half_ms":
               1e3 * (stats.percentile(late, 50) or 0),
               "compiles": rec["compiles"], **e2e}
        rows.append(row)
        bench.log(json.dumps(row))
        queues = driver.new_queues()
        n = [10 ** 6 * (i + 1)]
        bench._run_until_idle(cluster, queues, n)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
