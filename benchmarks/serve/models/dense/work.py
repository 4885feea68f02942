"""Operations and bytes the dense decoder's served work needs, counted from
shapes.

``a`` is the model's :class:`Sizes`.  The counts are the algorithm's, not
an implementation's: attention FLOPs count each query head against its own
KV head only, and KV bytes count each valid token's K and V of one layer
once, whatever tile the kernel moves.
"""
from __future__ import annotations

BF16 = 2                    # bytes


def layer_matmul_params(a) -> int:
    d, h, hk, dh, f = a.d_model, a.n_heads, a.n_kv_heads, a.head_dim(), a.d_ff
    return d * h * dh + 2 * d * hk * dh + h * dh * d + 3 * d * f


def matmul_params(a) -> int:
    """Weights one token multiplies through the decoder layers."""
    return a.n_layers * layer_matmul_params(a)


def head_flops(a) -> int:
    return 2 * a.d_model * a.vocab


def attention_flops(a, ctx: int) -> int:
    """One token attending over ``ctx`` keys (itself included), all
    layers: scores and the weighted sum, 2 FLOPs per multiply-add each."""
    return 4 * a.n_layers * ctx * a.n_heads * a.head_dim()


def decode_token_flops(a, in_pages: int) -> int:
    """One decoded token whose stream holds ``in_pages`` tokens before it."""
    return 2 * matmul_params(a) + attention_flops(a, in_pages + 1) \
        + head_flops(a)


def prefill_flops(a, skip: int, prompt: int) -> int:
    """Positions ``skip .. prompt-1`` computed, logits at the last."""
    n = prompt - skip
    # sum over p in [skip, prompt) of (p + 1) keys
    keys = (skip + 1 + prompt) * n // 2
    return 2 * matmul_params(a) * n + 4 * a.n_layers * keys * a.n_heads \
        * a.head_dim() + head_flops(a)


def paged_attn_work(a, in_pages: int):
    """(FLOPs, bytes) of one stream's paged decode attention over every
    layer (each runs the paged kernel): per layer, the valid pool tokens'
    K and V, the current token's K and V, the query and the output, in
    bf16."""
    h, hk, dh = a.n_heads, a.n_kv_heads, a.head_dim()
    flops = 4 * h * dh * (in_pages + 1)
    nbytes = (in_pages + 1) * 2 * hk * dh * BF16 + 2 * h * dh * BF16
    return a.n_layers * flops, a.n_layers * nbytes


def token_row_elems(a) -> int:
    """Elements of KV one token holds in the pool: K and V of every layer."""
    return 2 * a.n_layers * a.n_kv_heads * a.head_dim()


def kv_row_bytes(a) -> int:
    """One token's K and V over every layer, in bf16."""
    return token_row_elems(a) * BF16
