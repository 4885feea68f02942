"""Serving paths: cache init, prefill, and single-token decode per family.

``decode_step`` is the dry-run's ``serve_step``: one new token against a KV /
SSM-state cache of the cell's sequence length.  Caches are stacked on a
leading layer axis and threaded through the layer scan as scan inputs/outputs,
so decode HLO is O(1) in depth like the forward pass.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import rmsnorm, swiglu
from .moe import moe_apply
from .ssm import ssm_block
from .transformer import (Params, _embed, attn_decode,
                          attn_decode_paged, attn_prefill,
                          attn_prefill_cached, cross_apply, enc_kv_of,
                          logits_fn)

Cache = Dict[str, Any]

# families whose decode KV can live in LeaseEngine pool pages (an SSM state
# is not position-addressable block-wise; moe pages BOTH its cache stacks
# through named pools interleaved in one token row)
PAGED_FAMILIES = ("dense", "vlm", "moe")


class StackSpec(NamedTuple):
    """One paged KV cache stack: which params/cache it belongs to and where
    its segment lives inside the engine's interleaved pool token row."""
    pool: str           # LeaseEngine pool name
    params_key: str     # p[...] stacked layer params
    cache_keys: Tuple[str, str]   # dense-cache (k, v) names for this stack
    n_layers: int       # layers in this stack
    kind: str           # "mlp" | "moe" (the layer body after attention)
    offset: int         # element column offset of the segment in the row
    token_elems: int    # unpadded elements per token (2 * n_layers*hk*dh)


def pool_layout(cfg: ArchConfig) -> List[StackSpec]:
    """Ordered cache stacks of a paged family and their token-row layout.

    The single source of truth shared by the models (static ``k_off`` /
    ``v_off`` per layer), the serving engine (``kv_pools`` construction --
    ``ServingCluster`` asserts the engine computed the same offsets), and
    the differential tests.  Each stack's per-token segment packs all its
    layers' K then all its layers' V and is lane-padded; segments are laid
    out back to back in forward-pass order, so the moe family's leading
    dense stack comes first.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"no paged layout for family {cfg.family!r}")
    hkd = cfg.n_kv_heads * cfg.head_dim()
    if cfg.family == "moe":
        stacks = []
        if cfg.first_dense_layers:
            stacks.append(("dense", "dense_layers", ("dk", "dv"),
                           cfg.first_dense_layers, "mlp"))
        stacks.append(("moe", "layers", ("k", "v"),
                       cfg.n_layers - cfg.first_dense_layers, "moe"))
    else:
        stacks = [("kv", "layers", ("k", "v"), cfg.n_layers, "mlp")]
    from ..kernels.tardis_lease.kernel import LANES
    out, off = [], 0
    for pool, pkey, ckeys, n, kind in stacks:
        te = 2 * n * hkd
        out.append(StackSpec(pool, pkey, ckeys, n, kind, off, te))
        off += -(-te // LANES) * LANES
    return out


def _attn_cache(cfg, n, b, t, dtype):
    hk, dh = cfg.n_kv_heads, cfg.head_dim()
    return (jnp.zeros((n, b, t, hk, dh), dtype),
            jnp.zeros((n, b, t, hk, dh), dtype))


def _ssm_cache(cfg, n, b, dtype):
    h, pd, ns = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    c = cfg.d_inner + 2 * cfg.ssm_state
    return (jnp.zeros((n, b, h, pd, ns), jnp.float32),
            jnp.zeros((n, b, cfg.conv_width - 1, c), dtype))


def init_cache(cfg: ArchConfig, b: int, t: int,
               enc_len: int = 0, dtype=jnp.bfloat16) -> Cache:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        k, v = _attn_cache(cfg, cfg.n_layers, b, t, dtype)
        return {"k": k, "v": v}
    if fam == "moe":
        n_moe = cfg.n_layers - cfg.first_dense_layers
        k, v = _attn_cache(cfg, n_moe, b, t, dtype)
        out = {"k": k, "v": v}
        if cfg.first_dense_layers:
            dk, dv = _attn_cache(cfg, cfg.first_dense_layers, b, t, dtype)
            out.update(dk=dk, dv=dv)
        return out
    if fam == "ssm":
        s, c = _ssm_cache(cfg, cfg.n_layers, b, dtype)
        return {"state": s, "conv": c}
    if fam == "hybrid":
        n_app = cfg.n_layers // cfg.attn_every
        s, c = _ssm_cache(cfg, cfg.n_layers, b, dtype)
        ak, av = _attn_cache(cfg, n_app, b, t, dtype)
        return {"state": s, "conv": c, "ak": ak, "av": av}
    if fam == "encdec":
        k, v = _attn_cache(cfg, cfg.n_layers, b, t, dtype)
        ck, cv = _attn_cache(cfg, cfg.n_layers, b, enc_len, dtype)
        return {"k": k, "v": v, "ck": ck, "cv": cv}
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Decode step (one token)
# ---------------------------------------------------------------------------

def decode_step(cfg: ArchConfig, p: Params, cache: Cache, tokens,
                cur_idx) -> Tuple[Cache, jnp.ndarray]:
    """tokens: (B, 1) int32; cur_idx: int32 scalar (next cache slot) or a
    (B,) vector for attention-cache families decoding a continuous batch
    (each request at its own position).

    Returns (new_cache, logits (B, 1, V)).
    """
    fam = cfg.family
    x = jnp.take(p["embed"], tokens, axis=0)
    b = x.shape[0]
    new_cache = dict(cache)

    if fam in ("dense", "vlm", "moe"):
        from ..dist.annotate import replicate

        def body(xx, xs):
            layer, kc, vc = xs
            xx = replicate(xx)        # (B,1,D) is tiny: never gather weights
            y, kc, vc = attn_decode(layer["attn"], cfg, xx, kc, vc, cur_idx)
            xx = xx + y
            xn = rmsnorm(xx, layer["mlp_norm"], cfg.norm_eps)
            if fam == "moe":
                xx = xx + moe_apply(layer["moe"], cfg, xn)
            else:
                xx = xx + swiglu(layer["mlp"], xn)
            return xx, (kc, vc)
        if fam == "moe" and cfg.first_dense_layers:
            def dbody(xx, xs):
                layer, kc, vc = xs
                y, kc, vc = attn_decode(layer["attn"], cfg, xx, kc, vc, cur_idx)
                xx = xx + y
                xn = rmsnorm(xx, layer["mlp_norm"], cfg.norm_eps)
                return xx + swiglu(layer["mlp"], xn), (kc, vc)
            x, (dk, dv) = jax.lax.scan(
                dbody, x, (p["dense_layers"], cache["dk"], cache["dv"]))
            new_cache.update(dk=dk, dv=dv)
        x, (k, v) = jax.lax.scan(body, x, (p["layers"], cache["k"], cache["v"]))
        new_cache.update(k=k, v=v)

    elif fam == "ssm":
        def body(xx, xs):
            layer, s, cbuf = xs
            y, (s2, c2) = ssm_block(layer["ssm"], cfg, xx,
                                    decode_state=(s, cbuf))
            return xx + y, (s2, c2)
        x, (s2, c2) = jax.lax.scan(
            body, x, (p["layers"], cache["state"], cache["conv"]))
        new_cache.update(state=s2, conv=c2)

    elif fam == "hybrid":
        n_app = cfg.n_layers // cfg.attn_every
        per = cfg.attn_every
        states, convs, aks, avs = [], [], [], []

        def body(xx, xs):
            layer, s, cbuf = xs
            y, (s2, c2) = ssm_block(layer["ssm"], cfg, xx,
                                    decode_state=(s, cbuf))
            return xx + y, (s2, c2)
        for a in range(n_app):
            group = jax.tree.map(lambda t_, a=a: t_[a], p["groups"])
            sl = jax.lax.dynamic_slice_in_dim(cache["state"], a * per, per)
            cl = jax.lax.dynamic_slice_in_dim(cache["conv"], a * per, per)
            x, (s2, c2) = jax.lax.scan(body, x, (group, sl, cl))
            states.append(s2)
            convs.append(c2)
            y, kc, vc = attn_decode(p["shared"]["attn"], cfg, x,
                                    cache["ak"][a], cache["av"][a], cur_idx)
            x = x + y
            xn = rmsnorm(x, p["shared"]["mlp_norm"], cfg.norm_eps)
            x = x + swiglu(p["shared"]["mlp"], xn)
            aks.append(kc)
            avs.append(vc)
        new_cache.update(state=jnp.concatenate(states),
                         conv=jnp.concatenate(convs),
                         ak=jnp.stack(aks), av=jnp.stack(avs))

    elif fam == "encdec":
        def body(xx, xs):
            layer, kc, vc, ck, cv = xs
            y, kc, vc = attn_decode(layer["attn"], cfg, xx, kc, vc, cur_idx)
            xx = xx + y
            xx = xx + cross_apply(layer["cross"], cfg, xx, (ck, cv))
            xn = rmsnorm(xx, layer["mlp_norm"], cfg.norm_eps)
            return xx + swiglu(layer["mlp"], xn), (kc, vc)
        x, (k, v) = jax.lax.scan(
            body, x, (p["dec_layers"], cache["k"], cache["v"],
                      cache["ck"], cache["cv"]))
        new_cache.update(k=k, v=v)
    else:
        raise ValueError(fam)

    x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
    return new_cache, logits_fn(cfg, p, x)


def decode_step_paged(cfg: ArchConfig, p: Params, pool_rows, page_rows,
                      lengths, tokens, *, chunk: int,
                      interpret: bool = False, use_kernel=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step where every KV byte lives in LeaseEngine pool pages.

    ``pool_rows``: the engine pool's (n_blocks*chunk, token_row) view (one
    lane-padded row per token, all layers packed); ``page_rows``: (B, P)
    int32 per-request page tables (entries past a request's pages clamped
    to a valid id -- they are masked by ``lengths``); ``lengths``: (B,)
    int32 tokens already in pages (== the decode position), 0 for an empty
    slot (every request holds its prompt, so a real row is never 0): its
    token is computed and not appended; ``tokens``: (B, 1) int32.  Returns (new_pool_rows, logits (B, 1, V)): every
    layer's fresh KV for the new token is accumulated into ONE token row
    and scattered into its page by the ``tardis_lease`` append kernel --
    no host round trip, no dense per-request cache anywhere.

    The layer loop is unrolled (the pool is one shared buffer, not a
    per-layer scan operand); serving configs keep n_layers small, and the
    unrolled body is bit-identical to the scanned dense path.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged decode supports attention-cache families, "
            f"not {cfg.family!r}")
    from ..dist.annotate import replicate
    from ..kernels.tardis_lease.kernel import scatter_rows

    x = jnp.take(p["embed"], tokens, axis=0)
    b = x.shape[0]
    hkd = cfg.n_kv_heads * cfg.head_dim()
    lengths = jnp.asarray(lengths, jnp.int32)
    # one token row spanning EVERY cache stack's segment: the moe family's
    # dual stacks accumulate into the same buffer at their pool offsets and
    # land in the page together, in the single scatter below
    row_buf = jnp.zeros((b, pool_rows.shape[1]), pool_rows.dtype)
    for spec in pool_layout(cfg):
        for li in range(spec.n_layers):
            layer = jax.tree.map(lambda t, li=li: t[li], p[spec.params_key])
            if not (cfg.family == "moe" and spec.kind == "mlp"):
                # the dense decode path replicates inside the moe/dense
                # scan bodies but not in moe's leading dense stack --
                # mirror it exactly (replicate is numerically identity)
                x = replicate(x)
            with jax.named_scope("attention"):
                y, kd, vd = attn_decode_paged(
                    layer["attn"], cfg, x, pool_rows, page_rows, lengths,
                    li * hkd, (spec.n_layers + li) * hkd,
                    pool_off=spec.offset, chunk=chunk, interpret=interpret,
                    use_kernel=use_kernel)
                x = x + y
            with jax.named_scope("mlp"):
                x = x + _mlp(cfg, spec.kind, layer, x)
            with jax.named_scope("kv_append"):
                k_off = spec.offset + li * hkd
                v_off = spec.offset + (spec.n_layers + li) * hkd
                row_buf = row_buf.at[:, k_off:k_off + hkd].set(
                    kd.reshape(b, hkd))
                row_buf = row_buf.at[:, v_off:v_off + hkd].set(
                    vd.reshape(b, hkd))
    # ONE append per step: the token's whole row (every stack's, every
    # layer's K and V) lands in its page via the scalar-prefetched scatter
    # kernel
    with jax.named_scope("kv_append"):
        flat_idx = (page_rows[jnp.arange(b), lengths // chunk] * chunk
                    + lengths % chunk)
        # an empty slot's row goes past the pool's end: the scatter skips it
        flat_idx = jnp.where(lengths > 0, flat_idx, pool_rows.shape[0])
        pool_rows = scatter_rows(pool_rows, flat_idx, row_buf,
                                 interpret=interpret)
    with jax.named_scope("head"):
        x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
        return pool_rows, logits_fn(cfg, p, x)


def _mlp(cfg: ArchConfig, kind: str, layer, x):
    """A layer's feed-forward residual branch (dense or moe)."""
    xn = rmsnorm(x, layer["mlp_norm"], cfg.norm_eps)
    if kind == "moe":
        return moe_apply(layer["moe"], cfg, xn)
    return swiglu(layer["mlp"], xn)


# ---------------------------------------------------------------------------
# Prefill: full forward that also materializes the caches
# ---------------------------------------------------------------------------

def _last_logits(cfg, p, x, last_idx):
    """Logits at the prompt's true last position: ``last_idx=None`` keeps
    the trailing position (the unpadded case); a traced index lets callers
    right-pad prompts to a shape bucket (bounding retraces) and still read
    the real last token -- causality makes positions < last_idx identical
    bits either way."""
    if last_idx is None:
        return logits_fn(cfg, p, x[:, -1:])
    return logits_fn(cfg, p, jax.lax.dynamic_slice_in_dim(
        x, jnp.asarray(last_idx, jnp.int32), 1, 1))


def prefill(cfg: ArchConfig, p: Params, batch, cache_len: int,
            dtype=jnp.bfloat16, last_idx=None) -> Tuple[Cache, jnp.ndarray]:
    """Processes the prompt, returns (cache, last-token logits)."""
    fam = cfg.family
    if fam == "encdec":
        return _encdec_prefill(cfg, p, batch, cache_len)
    x = _embed(cfg, p, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache: Cache = {}

    if fam in ("dense", "vlm", "moe"):
        def make_body(kind):
            def body(xx, layer):
                with jax.named_scope("attention"):
                    y, (kc, vc) = attn_prefill(layer["attn"], cfg, xx,
                                               positions, cache_len)
                    xx = xx + y
                with jax.named_scope("mlp"):
                    xx = xx + _mlp(cfg, kind, layer, xx)
                return xx, (kc.astype(dtype), vc.astype(dtype))
            return body
        if fam == "moe" and cfg.first_dense_layers:
            x, (dk, dv) = jax.lax.scan(make_body("mlp"), x,
                                       p["dense_layers"])
            cache.update(dk=dk, dv=dv)
        x, (k, v) = jax.lax.scan(make_body("moe" if fam == "moe" else "mlp"),
                                 x, p["layers"])
        cache.update(k=k, v=v)
    elif fam == "ssm":
        def body(xx, layer):
            y, (st, cv) = ssm_block(layer["ssm"], cfg, xx)
            return xx + y, (st, cv.astype(dtype))
        x, (st, cv) = jax.lax.scan(body, x, p["layers"])
        cache.update(state=st, conv=cv)
    elif fam == "hybrid":
        n_app = cfg.n_layers // cfg.attn_every
        states, convs, aks, avs = [], [], [], []

        def body(xx, layer):
            y, (st, cv) = ssm_block(layer["ssm"], cfg, xx)
            return xx + y, (st, cv.astype(dtype))
        for a in range(n_app):
            group = jax.tree.map(lambda t_, a=a: t_[a], p["groups"])
            x, (st, cv) = jax.lax.scan(body, x, group)
            states.append(st)
            convs.append(cv)
            y, (kc, vc) = attn_prefill(p["shared"]["attn"], cfg, x,
                                       positions, cache_len)
            x = x + y
            xn = rmsnorm(x, p["shared"]["mlp_norm"], cfg.norm_eps)
            x = x + swiglu(p["shared"]["mlp"], xn)
            aks.append(kc.astype(dtype))
            avs.append(vc.astype(dtype))
        cache.update(state=jnp.concatenate(states),
                     conv=jnp.concatenate(convs),
                     ak=jnp.stack(aks), av=jnp.stack(avs))
    with jax.named_scope("head"):
        x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
        return cache, _last_logits(cfg, p, x, last_idx)


def prefill_suffix(cfg: ArchConfig, p: Params, batch, cache: Cache,
                   prefix_len: int, last_idx=None) -> Tuple[Cache,
                                                            jnp.ndarray]:
    """Chunked prefill that skips the prompt's leased prefix.

    ``cache`` arrives with its first ``prefix_len`` slots already holding
    the prefix KV (materialized from the serving engine's paged pool);
    ``batch["tokens"]`` carries only the suffix.  Each suffix query attends
    over [leased prefix KV; its own causal suffix KV], so the prefix's
    attention + MLP/MoE flops are skipped entirely.  Attention-cache
    families only (an SSM state is not position-addressable block-wise);
    the moe family runs its leading dense stack and its moe stack through
    the same cached-prefill attention, each against its own cache stack.
    """
    fam = cfg.family
    if fam not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"prefix-KV suffix prefill supports attention-cache families, "
            f"not {fam!r}")
    x = _embed(cfg, p, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(
        prefix_len + jnp.arange(s, dtype=jnp.int32), (b, s))

    def make_body(kind):
        def body(xx, xs):
            layer, kc, vc = xs
            with jax.named_scope("attention"):
                y, kc, vc = attn_prefill_cached(layer["attn"], cfg, xx,
                                                positions, kc, vc,
                                                prefix_len)
                xx = xx + y
            with jax.named_scope("mlp"):
                xx = xx + _mlp(cfg, kind, layer, xx)
            return xx, (kc, vc)
        return body

    out: Cache = {}
    for spec in pool_layout(cfg):
        ck, cv = spec.cache_keys
        x, (k, v) = jax.lax.scan(make_body(spec.kind), x,
                                 (p[spec.params_key], cache[ck], cache[cv]))
        out[ck], out[cv] = k, v
    with jax.named_scope("head"):
        x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
        return out, _last_logits(cfg, p, x, last_idx)


def _encdec_prefill(cfg, p, batch, cache_len, dtype=jnp.bfloat16):
    enc = batch["frames"].astype(p["embed"].dtype)
    b, se, _ = enc.shape
    epos = jnp.broadcast_to(jnp.arange(se, dtype=jnp.int32), (b, se))

    def ebody(xx, layer):
        from .transformer import attn_apply
        xx = xx + attn_apply(layer["attn"], cfg, xx, epos, causal=False)
        xn = rmsnorm(xx, layer["mlp_norm"], cfg.norm_eps)
        return xx + swiglu(layer["mlp"], xn), None
    enc, _ = jax.lax.scan(ebody, enc, p["enc_layers"])

    x = jnp.take(p["embed"], batch["tokens"], axis=0)
    sd = x.shape[1]
    dpos = jnp.broadcast_to(jnp.arange(sd, dtype=jnp.int32), (b, sd))

    def dbody(xx, layer):
        y, (kc, vc) = attn_prefill(layer["attn"], cfg, xx, dpos, cache_len)
        xx = xx + y
        ck, cv = enc_kv_of(layer["cross"], cfg, enc)
        xx = xx + cross_apply(layer["cross"], cfg, xx, (ck, cv))
        xn = rmsnorm(xx, layer["mlp_norm"], cfg.norm_eps)
        return xx + swiglu(layer["mlp"], xn), (
            kc.astype(dtype), vc.astype(dtype),
            ck.astype(dtype), cv.astype(dtype))
    x, (k, v, ck, cv) = jax.lax.scan(dbody, x, p["dec_layers"])
    x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
    return {"k": k, "v": v, "ck": ck, "cv": cv}, logits_fn(cfg, p, x[:, -1:])
