#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chip it runs on.

    python3 benchmarks/serve/bench.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<config>.json``: published sizes,
what was cut, the page pool, and the ``model`` it is) and a traffic mix
(``traffic/<mix>.json``).  The configuration's ``model`` names a package,
``models/<model>/``, that gives the harness everything that depends on the
model: its sizes, the program's family and fields, the weights, the plain
reference and the work counts the rooflines read (the contract is in
``models/dense/__init__.py``).  So a new model is new files only:
``models/<model>/``, ``configs/<config>.json``, ``traffic/<mix>.json`` and
``limits/<workload>.json``, with entries in ``BENCHMARK.json``; no file
already here changes.  A model whose vocabulary is cut to a chip's share
states the cut vocabulary in its sizes: traffic and the warm-up draw token
ids from ``sizes.vocab``.

The run, in one process:

1. set-up: weights made on the device from the seed (bf16), one
   ``ServingCluster`` replica over the configuration's page pool, and a
   warm-up that runs every shape the mix can reach (every own-prompt length
   of the mix's support through each prefix state, every decode batch size,
   the lease engine's batched shapes), repeating until a pass compiles
   nothing;
2. the window: an open loop that moves each request onto the queue when it
   is due and runs one scheduler tick while anything is queued or live,
   stamping every token on the host clock when its tick returns;
3. with ``--trace 1``, a profiler trace of a few seconds of the window,
   reduced to the per-layer metrics (``metrics/<name>.py``);
4. the check: once the window has closed and the program's device state is
   freed, a sample of finished requests drawn from the seed, with the
   longest among them, is run through the float32 reference (the
   model's ``logits_at``); the widest gap by which a served token's logit
   lies below the reference's best, in units of that position's logit
   RMS, must stay under the cell's limit (``limits/<workload>.json``).  With
   ``--control 1`` the tokens the float8 control puts first are judged in
   place of the served ones, through the same limit: such a run has to
   come out not correct.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``).  It exits non-zero, with no result,
when JAX finds no TPU or fewer chips than the cell asks for, or when the
program is missing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import driver  # noqa: E402
import stats  # noqa: E402
import tracing  # noqa: E402
import traffic  # noqa: E402

WARM_SEED = 7              # the warm-up's own prompt tokens
WARM_GROUPS = 4            # distinct requester rows one lease wave can hold
WARM_PASSES = 4            # warm passes at most; stop at one that compiles 0
TRACE_AT = 0.4             # share of the window before the trace starts
TRACE_SECONDS = 3.0
CHECK_STREAM = 0x5EED      # salts the seed for the check's sample
CHECK_BLOCK = 512          # reference sequences are padded to a multiple


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    conf: Dict
    mix: Dict
    limit: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    model: Any                # the package models/<model>/
    sizes: Any                # model.sizes(conf)

    @property
    def serving(self) -> Dict:
        return self.conf["serving"]


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_model(bench_dir: str, name: str):
    """The package ``models/<name>/`` of the benchmark's directory, as
    ``models.<name>``."""
    full = f"models.{name}"
    if full not in sys.modules:
        path = os.path.join(bench_dir, "models", name)
        spec = importlib.util.spec_from_file_location(
            full, os.path.join(path, "__init__.py"),
            submodule_search_locations=[path])
        mod = importlib.util.module_from_spec(spec)
        sys.modules[full] = mod
        spec.loader.exec_module(mod)
    return sys.modules[full]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell as ``<root>/BENCHMARK.json`` defines it; traffic, limits
    and models are read from the benchmark's directory (its first path)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    here = os.path.join(root, bench["paths"][0])
    w = next((x for x in bench["workloads"] if x["name"] == workload), None)
    if w is None:
        raise SystemExit(f"bench: no workload {workload!r}")
    c = next(x for x in bench["configs"] if x["name"] == w["config"])
    with open(os.path.join(root, c["file"])) as f:
        conf = json.load(f)
    mix = traffic.load_mix(os.path.join(here, "traffic",
                                        w["traffic"] + ".json"))
    with open(os.path.join(here, "limits", workload + ".json")) as f:
        limit = json.load(f)
    model = load_model(here, conf["model"])
    return Cell(workload, int(w["chips"]), c["name"], conf, mix, limit,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                [m for m in bench["per_layer"] if _applies(m, workload)],
                model, model.sizes(conf))


class Compiles:
    """Backend compiles and persistent-cache hits, from ``jax.monitoring``."""

    def __init__(self, jax):
        self.n, self.seconds, self.hits = 0, 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Run:
    """What the per-layer readers see."""
    ticks: List[Dict]
    counters_open: Dict[str, int]
    counters_close: Dict[str, int]
    model: Any
    sizes: Any
    peaks: Dict
    trace: Optional[Dict]

    def counter_delta(self, name: str) -> int:
        return self.counters_close[name] - self.counters_open[name]


def _run_until_idle(cluster, queues, n):
    while driver.busy(cluster, queues):
        driver.tick(cluster, queues, n[0])
        n[0] += 1


def _all_pairs(k: int) -> List[int]:
    """A shortest sequence over ``range(k)`` holding every ordered pair as
    neighbours (a de Bruijn sequence of order 2, closed)."""
    seq, seen, cur = [0], set(), 0
    while len(seen) < k * k:
        nxt = next((b for b in reversed(range(k)) if (cur, b) not in seen),
                   None)
        if nxt is None:
            raise AssertionError("no pair left")
        seen.add((cur, nxt))
        seq.append(nxt)
        cur = nxt
    return seq


def warm(cluster, cell: Cell, comp: Compiles) -> int:
    """Run every shape the cell's mix can reach; returns the passes made."""
    s = cell.sizes
    mix = cell.mix
    prefixes = traffic.shared_prompts(mix, s.vocab)
    head = traffic.unique_head_tokens(mix)
    lengths = traffic.support_points(mix["prompt_tokens"])
    serving = cell.serving
    rng = np.random.default_rng(WARM_SEED)
    queues = driver.new_queues()
    n = [0]
    rid = [-1]

    def submit(prompt, max_new):
        if head:    # a head of its own, as every request of the mix has
            prompt = np.concatenate(
                [rng.integers(1, s.vocab, head).astype(np.int32), prompt])
        driver.submit(queues, rid[0], prompt, max_new)
        rid[0] -= 1

    if prefixes:
        pages = sum(len(p) for p in prefixes) // serving["page_tokens"]
        driver.warm_lease_shapes(cluster, WARM_GROUPS, pages)
    # each tenant after each tenant, with streams alive across the turn,
    # so every prefix state (covered, evicted, relocated) is met
    # (the chain runs on from one length to the next, so the closing pair
    # comes from the next length's first admission)
    order = _all_pairs(len(prefixes))[:-1] if prefixes else [-1]
    full = bool(mix.get("batch_always_full"))
    passes = 0
    for passes in range(1, WARM_PASSES + 1):
        c0 = comp.n
        for length in lengths:
            own = rng.integers(1, s.vocab, length).astype(np.int32)
            for t in order:
                # a full-batch mix never decodes below its full batch: its
                # warm admissions finish at their first token
                submit(np.concatenate([prefixes[t], own]) if t >= 0 else own,
                       1 if full else 3)
                driver.tick(cluster, queues, n[0])
                n[0] += 1
        _run_until_idle(cluster, queues, n)
        # every decode batch size: max_batch streams join together and
        # finish one per tick (a full-batch mix: all after one step)
        own = rng.integers(1, s.vocab, lengths[0]).astype(np.int32)
        for b in range(serving["max_batch"]):
            t = b % len(prefixes) if prefixes else -1
            submit(np.concatenate([prefixes[t], own]) if t >= 0 else own,
                   2 if full else b + 2)
        _run_until_idle(cluster, queues, n)
        log(f"warm pass {passes}: {comp.n - c0} backend compiles")
        if passes > 1 and comp.n == c0:
            break
    return passes


def window(cluster, cell: Cell, reqs, seconds: float, comp: Compiles,
           trace: bool):
    """The open loop; returns its record."""
    import jax
    queues = driver.new_queues()
    objs, times, lateness, ticks = {}, {}, [], []
    tdir, trace_state, traced = None, "off", [0, 0]
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        trace_state = "armed"
    i, n = 0, 0
    c_open = driver.counters(cluster)
    comp0 = comp.n
    t_open = time.perf_counter()
    t_end = t_open + seconds
    trace_t = [0.0, 0.0]
    pages_peak = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while i < len(reqs) and t_open + reqs[i].due <= now:
            r = reqs[i]
            objs[r.idx] = driver.submit(queues, r.idx, r.prompt, r.max_new)
            times[r.idx] = []
            lateness.append(now - (t_open + r.due))
            i += 1
        if not driver.busy(cluster, queues):
            nxt = t_open + reqs[i].due if i < len(reqs) else t_end
            time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        if trace_state == "armed" and now >= t_open + TRACE_AT * seconds:
            jax.profiler.start_trace(tdir)
            trace_state, traced[0] = "on", len(ticks)
            trace_t[0] = time.perf_counter()
        elif trace_state == "on" and now >= trace_t[0] + TRACE_SECONDS:
            trace_t[1] = time.perf_counter()
            jax.profiler.stop_trace()
            trace_state, traced[1] = "done", len(ticks)
        before = {id(st): st for st in driver.active(cluster)}
        span = (jax.profiler.TraceAnnotation("tick") if trace_state == "on"
                else contextlib.nullcontext())
        ts = time.perf_counter()
        with span:
            driver.tick(cluster, queues, n)
        te = time.perf_counter()
        n += 1
        pages_peak = max(pages_peak, driver.pages_in_use(cluster))
        after = driver.active(cluster)
        now_ids = {id(st) for st in after}
        finished = [st for k, st in before.items() if k not in now_ids]
        admitted = [st for st in after if id(st) not in before]
        decoded = after + finished
        for st in decoded:
            rid = driver.stream_request(st).rid
            got = len(driver.stream_tokens(st))
            times[rid].extend([te] * (got - len(times[rid])))
        ticks.append({
            "start": ts, "end": te,
            "admitted": [(len(driver.stream_request(st).prompt),
                          driver.stream_skip(cluster, st)) for st in admitted],
            "decoded": [driver.stream_length(st) - 1 for st in decoded]})
    if trace_state == "on":
        trace_t[1] = time.perf_counter()
        jax.profiler.stop_trace()
        traced[1] = len(ticks)
    t_close = max(t_end, ticks[-1]["end"] if ticks else t_end)
    rec = {
        "open": t_open, "close": t_close, "ticks": ticks, "times": times,
        "objs": objs, "lateness": lateness, "counters_open": c_open,
        "counters_close": driver.counters(cluster),
        "compiles": comp.n - comp0, "due": {r.idx: t_open + r.due
                                            for r in reqs},
        "pages_peak": pages_peak,
        "region_filled": driver.region_slots_filled(cluster)}
    if trace:
        record = tracing.extract(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        rec["trace"] = {"record": record,
                        "ticks": ticks[traced[0]:traced[1]],
                        "window_s": trace_t[1] - trace_t[0]}
    return rec


def end_to_end(rec) -> Dict[str, float]:
    due = [rec["due"][k] for k in sorted(rec["due"])]
    first = [rec["times"][k][0] if rec["times"].get(k) else None
             for k in sorted(rec["due"])]
    ttft = stats.ttft_samples(due, first, rec["close"])
    gaps = stats.token_gaps(rec["times"], rec["close"])
    out = {"ttft_p50_ms": stats.percentile(ttft, 50),
           "ttft_p95_ms": stats.percentile(ttft, 95),
           "itl_p95_ms": stats.percentile(gaps, 95),
           "tokens_per_s": stats.tokens_per_s(rec["times"], rec["open"],
                                              rec["close"])}
    return {k: (v * 1e3 if k.endswith("_ms") and v is not None else v)
            for k, v in out.items()}


def check_length(cell: Cell) -> int:
    sp = cell.mix.get("shared_prefix") or {}
    longest = (sp.get("tokens", 0) + traffic.unique_head_tokens(cell.mix)
               + cell.mix["prompt_tokens"]["max"]
               + cell.mix["output_tokens"]["max"])
    return -(-longest // CHECK_BLOCK) * CHECK_BLOCK


def sample(cell: Cell, seed: int, finished: List) -> List:
    """Finished ``(prompt, served tokens)`` pairs drawn from the seed, with
    the longest first, up to the mix's request count or until the served
    tokens reach its target."""
    chk = cell.mix["check"]
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), CHECK_STREAM])
    longest = max(range(len(finished)), key=lambda j: len(finished[j][1]))
    order = [longest] + [int(j) for j in rng.permutation(len(finished))
                         if j != longest]
    out, served = [], 0
    for j in order[:chk["max_requests"]]:
        out.append(finished[j])
        served += len(finished[j][1])
        if served >= chk["served_tokens"]:
            break
    return out


def check_rows(cell: Cell) -> int:
    """Scored positions per check, fixed per cell: the served-token target
    plus one longest output, in blocks of 256."""
    chk = cell.mix["check"]
    n = chk["served_tokens"] + cell.mix["output_tokens"]["max"]
    return -(-n // 256) * 256


def _gap_stats(ref, toks):
    """Per scored row: the token's gap below the reference's best, in units
    of the row's logit RMS, and whether it is the reference's top one."""
    import jax.numpy as jnp
    mx = jnp.max(ref, -1)
    rms = jnp.sqrt(jnp.mean(ref * ref, -1))
    got = jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
    return (mx - got) / rms, jnp.argmax(ref, -1) == toks


def gaps(cell: Cell, seed: int, picked: List, control: bool) -> Dict:
    """Widest gap by which a judged token's logit lies below the float32
    reference's best, in units of the position's logit RMS.  The judged
    tokens are the served ones; with ``control``, the tokens the float8
    control puts first at the same positions (same prompts, same served
    tokens before them) take their place, and the served ones' reading is
    kept beside them."""
    import jax
    import jax.numpy as jnp
    n_seq = cell.mix["check"]["max_requests"]
    length = check_length(cell)
    n_rows = check_rows(cell)
    seqs, flat, toks, owner = [], [], [], []
    for i, (prompt, out) in enumerate(picked):
        seqs.append(np.concatenate([prompt, np.asarray(out[:-1], np.int32)]))
        flat.extend(i * length + len(prompt) - 1 + np.arange(len(out)))
        toks.extend(out)
        owner.extend([i] * len(out))
    n = len(toks)
    seqs += [seqs[0]] * (n_seq - len(seqs))     # one compiled shape per cell
    flat = np.pad(np.asarray(flat, np.int64), (0, n_rows - n))
    toks = np.pad(np.asarray(toks, np.int32), (0, n_rows - n))
    t = time.perf_counter()
    ref = cell.model.logits_at(cell.sizes, seed, seqs, flat, length, "f32")
    stat = jax.jit(_gap_stats)
    served, _ = (np.asarray(x)[:n] for x in stat(ref, jnp.asarray(toks)))
    judged = (cell.model.logits_at(cell.sizes, seed, seqs, flat, length,
                                   "fp8")
              if control else jnp.asarray(toks))
    g, top1 = (np.asarray(x)[:n] for x in stat(ref, judged))
    log(f"reference: {time.perf_counter() - t:.3f} s for {len(picked)} "
        f"requests, {n} served tokens" + (" and the control" if control
                                           else ""))
    owner = np.asarray(owner)
    return {"widest_gap": float(g.max()), "tokens": n,
            "top1": int(top1.sum()), "served_widest_gap": float(served.max()),
            "per_request": [float(g[owner == i].max())
                            for i in range(len(picked))]}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             control: bool = False, save_trace: Optional[str] = None) -> Dict:
    """Everything but the look for a chip; returns the result object."""
    driver.import_program()
    import jax
    comp = Compiles(jax)
    cache_dir = driver.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(dev.device_kind)
    if peaks is None and dev.platform == "tpu":
        raise SystemExit(f"bench: no peaks for device {dev.device_kind!r}")
    s = cell.sizes
    params = cell.model.make_params(seed, s)
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    arch = driver.arch_config(cell.config_name, cell.model.FAMILY,
                              cell.model.arch_fields(s))
    cluster = driver.build_cluster(arch, params, cell.serving)
    del params
    page_bytes = driver.page_bytes(cluster)
    passes = warm(cluster, cell, comp)
    reqs = traffic.generate(cell.mix, seed, seconds, s.vocab)
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s:.3f} s: {comp.n} backend compiles "
        f"({comp.seconds:.3f} s), {comp.hits} persistent-cache hits, "
        f"{passes} warm passes; cache {cache_dir}")
    rec = window(cluster, cell, reqs, seconds, comp, trace)
    stats_ = dev.memory_stats() or {}
    peak = stats_.get("peak_bytes_in_use")
    e2e = end_to_end(rec)
    late = rec["lateness"]
    log(f"window {rec['close'] - rec['open']:.3f} s: {len(rec['ticks'])} "
        f"ticks, {len(reqs)} requests due; generator lateness p50 "
        f"{stats.percentile(late, 50) or 0:.6f} s, max "
        f"{max(late) if late else 0:.6f} s; backend compiles in window "
        f"{rec['compiles']}")
    log("window e2e: " + ", ".join(f"{k} {v}" for k, v in e2e.items()))
    sv = cell.serving
    held = {
        "weight_bytes": weight_bytes,
        "region_slots_filled": rec["region_filled"],
        "decode_pages_peak": rec["pages_peak"],
        "kv_bytes_held": (rec["region_filled"] + rec["pages_peak"])
        * page_bytes,
        "kv_bytes_reserved": (sv["n_prefix_blocks"] + sv["n_decode_pages"])
        * page_bytes}
    log(f"memory: weights {weight_bytes} B; region slots filled "
        f"{rec['region_filled']} of {sv['n_prefix_blocks']}, decode pages "
        f"allocated at most {rec['pages_peak']} of {sv['n_decode_pages']}: KV "
        f"{held['kv_bytes_held']} B held of "
        f"{held['kv_bytes_reserved']} B reserved; device peak {peak} B")
    finished = []
    for r in reqs:
        obj = rec["objs"].get(r.idx)
        if obj is not None and driver.request_done(obj):
            finished.append((r.prompt, driver.request_output(obj)))
    log(f"finished in window: {len(finished)} of {len(reqs)}")
    run = Run(rec["ticks"], rec["counters_open"], rec["counters_close"],
              cell.model, s, peaks or {}, rec.get("trace"))
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        for m in cell.per_layer:
            mod = importlib.import_module(f"metrics.{m['name']}")
            v = mod.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        record = rec["trace"]["record"]
        if save_trace:
            with open(save_trace, "w") as f:
                json.dump({"trace": record, "ticks": rec["trace"]["ticks"]}, f)
        breakdown = {"device_ops": tracing.top_ops(record),
                     "idle_gaps": tracing.idle_gaps(record)}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    log("metrics: " + json.dumps(metrics))
    # free the program's device state before the reference runs
    del rec["objs"]
    driver.release(cluster)
    del cluster
    t = time.perf_counter()
    picked = sample(cell, seed, finished)
    chk = gaps(cell, seed, picked, control) if picked else None
    limit = cell.limit["widest_gap"]["limit"]
    correct = chk is not None and chk["widest_gap"] <= limit
    if chk:
        judged = "the float8 control's" if control else "served"
        log(f"check: {len(picked)} requests, {chk['tokens']} {judged} "
            f"tokens judged, {chk['top1']} equal to the reference's top "
            f"token; served tokens' widest_gap {chk['served_widest_gap']}; "
            f"reference {time.perf_counter() - t:.3f} s")
    result = {
        "correct": bool(correct), "attempted": len(reqs),
        "failed": (1 if chk is None else
                   sum(g > limit for g in chk["per_request"])),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": cell.chips, "memory_peak_bytes": peak}}
    result["window"] = {
        "backend_compiles": rec["compiles"], "warm_passes": passes,
        "prompt_tokens_admitted": sum(p for t in rec["ticks"]
                                      for p, _ in t["admitted"]),
        "prefill_tokens_skipped": run.counter_delta(
            "prefix_prefill_tokens_skipped"), **held}
    if trace:
        tr = rec["trace"]
        result["device"]["busy_s"] = tracing.busy_ns(tr["record"]) * 1e-9
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = breakdown
    if control and chk:
        # what the served tokens read on the same sample, for comparison
        result["control"] = {"served_widest_gap": chk["served_widest_gap"]}
    result["check"] = {"widest_gap": {
        "value": None if chk is None else chk["widest_gap"],
        "limit": limit}}
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 control's tokens in place of the "
                         "served ones (a run that must come out not correct)")
    ap.add_argument("--save-trace", default=None,
                    help="write the reduced trace record to this file")
    return ap.parse_args(argv)


def add_libtpu_flags(cell: Cell) -> None:
    """Append the configuration's compiler flags to what the machine set,
    before JAX starts."""
    flags = cell.serving.get("libtpu_flags", [])
    if flags:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            [os.environ.get("LIBTPU_INIT_ARGS", "")] + flags).strip()


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    add_libtpu_flags(cell)
    driver.import_program()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: JAX found no TPU (platform {devs[0].platform!r})")
        return 3
    if len(devs) < cell.chips:
        log(f"bench: the cell needs {cell.chips} chips, JAX found "
            f"{len(devs)}")
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      bool(args.control), args.save_trace)
    for k, v in result["check"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
