"""Chip smoke: serve full-width tinyllama-1.1b on one TPU chip and check it.

    python chip_smoke.py

Drives the path a user drives, in this one process: the serve CLI's own
``parse_args`` / ``build`` / ``serve`` (:mod:`repro.launch.serve`) with
``--no-reduced``, so the model is tinyllama-1.1b at its published widths
(22 layers, d_model 2048, 32 heads over 4 KV heads, d_ff 5632, vocab
32000) with random float32 weights from seed 0.  One ``ServingCluster``
replica batches continuously over leased pool pages (4352 blocks of 16
tokens, bf16): 8 requests share a 64-token system prompt and take 4 batch
slots, so the first four prefill cold and write the prompt's 4 prefix
pages, and the last four hit them and prefill only their suffix.  Every
decode step runs the Pallas paged flash-decode kernel on every layer and
appends the token's KV through the Pallas scatter kernel; the lease table
kernels and the page gather run compiled too.  The request set is served
twice: the first pass compiles, the second is all prefix hits and is the
timed serve.

Correctness is checked against the dense-cache path (``prefill`` then
``decode_step``), fed the same prompts and, step by step, the tokens the
paged path chose.  The script fails when a logit differs by more than
``LOGIT_TOL`` times the reference logits' RMS, or when a token the paged
path chose is not the reference's greedy choice up to that tolerance.

It exits non-zero, and prints no result, when JAX finds no TPU or the
repository's ``src/`` is missing.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# The serve CLI's flags: one replica with 4 batch slots, 8 requests over a
# 64-token shared system prompt (4 pages of 16 tokens) plus 4-15 tokens of
# their own, 4 new tokens each.
SERVE_ARGV = ["--arch", "tinyllama-1.1b", "--no-reduced", "--replicas", "1",
              "--requests", "8", "--prefix-len", "64", "--prefix-block", "16",
              "--max-batch", "4", "--max-new", "4"]

# Tolerance on |paged - dense| logits, relative to the dense logits' RMS.
# The two paths round at different points: KV pages hold bf16 (8-bit
# mantissa, relative rounding 2**-9), a suffix prefill attends over the
# leased prefix in bf16 where the dense prefill uses float32, the paged
# kernel sums its online softmax in another order than the dense einsum,
# and the chip's default float32 matmul rounds operands to bf16.  A few
# 2**-9 per layer over 22 residual layers, summed in quadrature, is about
# 3% of the logits' scale; 5% leaves room above that.  A page or offset
# wired wrong moves logits by a large fraction of their scale.
LOGIT_TOL = 0.05


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def dense_reference(cfg, params, prompts, tokens):
    """Logits of the dense-cache path, teacher-forced on ``tokens`` (n,
    max_new): the prefill's last-position logits (n, V) and each decode
    step's (n, max_new - 1, V)."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode_step, prefill

    n, max_new = tokens.shape
    lens = np.asarray([len(p) for p in prompts], np.int32)
    width = int(-(-lens.max() // 16) * 16)
    padded = np.zeros((n, width), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    cache_len = width + max_new

    def one(p, toks, last):
        cache, logits = prefill(cfg, p, {"tokens": toks[None]}, cache_len,
                                last_idx=last)
        return cache, logits[0, 0]

    cache, first = jax.jit(jax.vmap(one, in_axes=(None, 0, 0)))(
        params, jnp.asarray(padded), jnp.asarray(lens - 1))
    # (n, L, 1, T, hk, dh) per request -> (L, n, T, hk, dh) batched
    cache = {k: jnp.moveaxis(v[:, :, 0], 0, 1) for k, v in cache.items()}
    step = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
    steps = []
    for t in range(max_new - 1):
        cache, logits = step(params, cache, jnp.asarray(tokens[:, t:t + 1]),
                             jnp.asarray(lens + t))
        steps.append(np.asarray(logits[:, 0]))
    return np.asarray(first), np.stack(steps, axis=1)


def paged_logits(trace, reqs):
    """The served path's logits per request out of the cluster's trace:
    admission prefill (n, V) and each decode step (n, max_new - 1, V)."""
    rid_row = {r.rid: i for i, r in enumerate(reqs)}
    n, max_new = len(reqs), reqs[0].max_new
    first, steps = None, None
    for ev in trace:
        if ev["ev"] == "admit":
            logits = ev["logits"][0, -1]
            if first is None:
                first = np.zeros((n,) + logits.shape, np.float32)
                steps = np.zeros((n, max_new - 1) + logits.shape, np.float32)
            first[rid_row[ev["rid"]]] = logits
        else:
            for b, rid in enumerate(ev["rids"]):
                i = rid_row[rid]
                t = int(ev["lengths"][b]) - len(reqs[i].prompt)
                steps[i, t] = ev["logits"][b, -1]
    return first, steps


def check_pass(name, reqs, trace, ref_first, ref_steps):
    """Compare one served pass with the dense reference; returns the
    largest relative logit difference (first decode step, all)."""
    first, steps = paged_logits(trace, reqs)
    paged = np.concatenate([first[:, None], steps], axis=1)
    ref = np.concatenate([ref_first[:, None], ref_steps], axis=1)
    rms = float(np.sqrt(np.mean(ref.astype(np.float64) ** 2)))
    tol = LOGIT_TOL * rms
    rel_first = float(np.max(np.abs(steps[:, 0] - ref_steps[:, 0]))) / rms
    rel_all = float(np.max(np.abs(paged - ref))) / rms
    out = np.stack([r.output for r in reqs])
    picked = np.take_along_axis(ref, out[:, :, None], axis=2)[:, :, 0]
    greedy = ref.argmax(axis=2)
    exact = int(np.sum(greedy == out))
    # a token differing from the reference's argmax must be a near tie:
    # its reference logit within the tolerance of the maximum
    gap = ref.max(axis=2) - picked
    print(f"{name}: logits |paged-dense|/rms(dense): first decode step "
          f"{rel_first:.6g}, all {rel_all:.6g} (tol {LOGIT_TOL}, rms "
          f"{rms:.6g}); greedy tokens equal {exact}/{out.size}, largest "
          f"near-tie gap {float(gap.max()):.6g}")
    if not rel_all <= LOGIT_TOL:
        fail(f"{name}: logits differ by {rel_all:.6g} x rms > {LOGIT_TOL}")
    if not float(gap.max()) <= tol:
        fail(f"{name}: a token is not the dense path's greedy choice "
             f"(gap {float(gap.max()):.6g} > {tol:.6g})")
    return rel_first, rel_all


def main() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from the repository")
    sys.path.insert(0, SRC)
    import jax

    from repro.configs import get_arch
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime import Request

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")

    compile_s = [0.0]
    cache_hits = [0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    t0 = time.perf_counter()
    args = serve.parse_args(SERVE_ARGV)
    cfg, cluster, reqs = serve.build(args)
    if cfg != get_arch("tinyllama-1.1b"):
        fail(f"served config is not the published one: {cfg}")
    eng = cluster.prefix_engine
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab}; pool {eng.n_blocks} blocks x "
          f"{eng.kv_chunk} tokens x {eng.kv_token_row}; "
          f"setup {time.perf_counter() - t0:.3f} s")
    print(f"prefix_engine.interpret: {eng.interpret}")
    if eng.interpret or not cluster.paged:
        fail("the served path would run its kernels in interpret mode")

    passes = []
    for name in ("pass1", "pass2"):
        batch = [Request(r.rid, r.prompt, max_new=r.max_new) for r in reqs]
        cluster.trace = []
        c0, t = compile_s[0], time.perf_counter()
        done, report = serve.serve(args, cluster, batch)
        jax.block_until_ready(eng.kv_rows_view())
        wall = time.perf_counter() - t
        served = sum(r.done for r in done)
        tokens = sum(len(r.output) for r in done)
        print(f"{name}: served {served}/{len(batch)} requests, {tokens} "
              f"tokens in {wall:.3f} s, of which backend compile "
              f"{compile_s[0] - c0:.3f} s; prefix_block_hits "
              f"{report['prefix_block_hits']}, prefill tokens skipped "
              f"{report['prefix_prefill_tokens_skipped']}, kv rows appended "
              f"{report['kv_tokens_appended']}")
        if served != len(batch):
            fail(f"{name}: {len(batch) - served} requests not served")
        passes.append((name, done, cluster.trace))
    cluster.trace = None
    if report["prefix_prefill_tokens_skipped"] <= 0:
        fail("no request was served from the leased prefix")

    # every kernel of the decode step is a native Mosaic custom call
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (
        eng.kv_rows_view(), np.zeros((args.max_batch, args.max_pages),
                                     np.int32),
        np.zeros(args.max_batch, np.int32),
        np.zeros((args.max_batch, 1), np.int32))]
    text = cluster._decode_paged_fn.lower(
        cluster.replicas[0].params(), *shapes).as_text()
    n_kernels = text.count("tpu_custom_call")
    print(f"decode step: {n_kernels} Pallas kernels compiled natively "
          f"(want {cfg.n_layers + 1})")
    if n_kernels < cfg.n_layers + 1:
        fail("the decode step does not run its Pallas kernels natively")

    t = time.perf_counter()
    params = cluster.replicas[0].params()
    tokens = np.stack([r.output for r in passes[0][1]])
    ref_first, ref_steps = dense_reference(
        cfg, params, [r.prompt for r in reqs], tokens)
    print(f"dense reference: {time.perf_counter() - t:.3f} s")
    for name, done, trace in passes:
        if not all(np.array_equal(a.output, b.output)
                   for a, b in zip(done, passes[0][1])):
            # pass 2 reads its prefix from pages pass 1 wrote: its tokens
            # may differ only at near ties, which check_pass bounds
            ref_first, ref_steps = dense_reference(
                cfg, params, [r.prompt for r in reqs],
                np.stack([r.output for r in done]))
        check_pass(name, done, trace, ref_first, ref_steps)

    print(f"total backend compile {compile_s[0]:.3f} s, persistent cache "
          f"hits {cache_hits[0]}; wall {time.perf_counter() - t0:.3f} s")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory {stats['peak_bytes_in_use']} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
