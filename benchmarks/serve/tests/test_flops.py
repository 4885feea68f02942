"""Model FLOPs and needed bytes against hand counts for both cells'
configurations, reached through the configuration's model."""
import pytest

import bench
import flops


@pytest.fixture(params=["mistral-nemo-12b.agent_shared",
                        "glm4-9b.reasoning_overload"])
def cell(request):
    return bench.load_cell(request.param)


HAND = {
    # d*h*dh + 2*d*hk*dh + h*dh*d + 3*d*f per layer; d*V head
    5120: dict(layer=5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120
               + 3 * 5120 * 14336, layers=10, head=5120 * 131072,
               row=2 * 10 * 8 * 128 * 2),
    4096: dict(layer=4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
               + 3 * 4096 * 13696, layers=20, head=4096 * 151552,
               row=2 * 20 * 2 * 128 * 2),
}


def test_layer_params_match_the_published_widths(cell):
    sizes, work = cell.sizes, cell.model.work
    hand = HAND[sizes.d_model]
    assert work.layer_matmul_params(sizes) == hand["layer"]
    assert work.matmul_params(sizes) == hand["layers"] * hand["layer"]
    assert work.head_flops(sizes) == 2 * hand["head"]
    assert cell.model.kv_row_bytes(sizes) == hand["row"]
    assert cell.model.token_row_elems(sizes) == hand["row"] // 2


def test_literal_counts():
    mc = bench.load_cell("mistral-nemo-12b.agent_shared")
    gc = bench.load_cell("glm4-9b.reasoning_overload")
    model, m, g = mc.model, mc.sizes, gc.sizes
    assert gc.model is model
    assert model.work.layer_matmul_params(m) == 272_629_760
    assert model.work.layer_matmul_params(g) == 203_948_032
    assert model.kv_row_bytes(m) == 40_960
    assert model.kv_row_bytes(g) == 20_480
    assert model.decode_token_flops(m, 999) == 6_958_612_480
    # per layer, times the layers that run the paged kernel (all of them)
    assert model.paged_attn_work(m, 1000) == (10 * 16_400_384,
                                              10 * 4_116_480)
    assert model.paged_attn_work(g, 1000) == (
        20 * 4 * 32 * 128 * 1001,
        20 * (1001 * 2 * 2 * 128 * 2 + 2 * 32 * 128 * 2))
    assert flops.append_bytes(model.kv_row_bytes(m), 16) == 1_310_720


def test_prefill_sums_each_position(cell):
    sizes, model = cell.sizes, cell.model
    skip, prompt = 4096, 4196
    by_hand = sum(2 * model.work.matmul_params(sizes)
                  + model.work.attention_flops(sizes, p + 1)
                  for p in range(skip, prompt)) \
        + model.work.head_flops(sizes)
    assert model.prefill_flops(sizes, skip, prompt) == by_hand
    # a full prefill of one token is one decode token with nothing before
    assert model.prefill_flops(sizes, 0, 1) == model.decode_token_flops(
        sizes, 0)


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert flops.roofline_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)
