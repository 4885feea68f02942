"""A model that only this fixture has: the dense decoder's arithmetic, with
its sizes read from the configuration's own keys (no ``arch_keys``) and
already small enough for the CPU."""
from models import dense

FAMILY = dense.FAMILY
arch_fields = dense.arch_fields
make_params = dense.make_params
logits_at = dense.logits_at
decode_token_flops = dense.decode_token_flops
prefill_flops = dense.prefill_flops
paged_attn_work = dense.paged_attn_work
kv_row_bytes = dense.kv_row_bytes
token_row_elems = dense.token_row_elems


def sizes(conf: dict) -> dense.Sizes:
    return dense.Sizes(
        n_layers=conf["layers"], d_model=conf["width"], n_heads=conf["heads"],
        n_kv_heads=conf["kv_heads"], d_head=conf["head_width"],
        d_ff=conf["mlp_width"], vocab=conf["vocab"],
        rope_theta=conf["rope_base"], norm_eps=conf["eps"])


def cpu_sizes(s: dense.Sizes) -> dense.Sizes:
    return s
