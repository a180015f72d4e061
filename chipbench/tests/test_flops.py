"""Useful-work counts of the dense family against hand counts, for both
configurations."""

import pytest

import bench

flops = bench.family("dense")


def config(name):
    return bench.load_config(name)[0]


# per layer: q and o at h*D = d, k and v at g*D, then the MLP's matrices
HAND_LAYER = {
    # 2 * 2560*2560 + 2 * 2560*(32*80) + 3 * 2560*6912
    "stablelm-3b": 13107200 + 13107200 + 53084160,
    # 2 * 3072*3072 + 2 * 3072*(2*128) + 2 * 3072*12288
    "starcoder2-3b": 18874368 + 1572864 + 75497472,
}


@pytest.mark.parametrize("name", sorted(HAND_LAYER))
def test_layer_params(name):
    assert flops.layer_params(config(name)) == HAND_LAYER[name]


@pytest.mark.parametrize("name", sorted(HAND_LAYER))
def test_request_flops_by_hand(name):
    cfg = config(name)
    L, d, V = 8, cfg["hidden_size"], cfg["vocab_size"]
    assert cfg["num_hidden_layers"] == L
    width = cfg["num_attention_heads"] * (d // cfg["num_attention_heads"])
    # prompt of 3, 2 served tokens: positions 0..3 run the layers (the last
    # served token is never fed back); they see 1+2+3+4 = 10 keys
    want = (2 * HAND_LAYER[name] * L * 4 + 4 * width * L * 10
            + 2 * d * V * 2)
    assert flops.request_flops(cfg, 3, 2) == want


def test_padded_heads_do_not_count():
    # starcoder2 pads 24 query heads to 32 in the program; the count is of 24
    cfg = config("starcoder2-3b")
    assert flops.layer_params(cfg) < 2 * 3072 * 32 * 128 + 2 * 3072 * 256 \
        + 2 * 3072 * 12288


@pytest.mark.parametrize("name", sorted(HAND_LAYER))
def test_act_elements(name):
    cfg = config(name)
    ff = cfg["intermediate_size"]
    # 2 prefills of 8 x 1920 rows and 5 ticks of 8 rows, 8 layers
    counters = {"prefills": 2, "batch_steps": 7, "batch": 8,
                "prefill_len": 1920}
    assert flops.act_elements(cfg, counters) == \
        (2 * 8 * 1920 + 5 * 8) * ff * 8
