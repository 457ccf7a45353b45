"""The benchmark's operation and byte counts, by hand at qwen3-32b widths."""
from chipbench import flops
from chipbench.bench import find_cell

SERVE = find_cell("qwen3-32b-serve.chat").config
TRAIN = find_cell("qwen3-32b-train.pack4k").config
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# D = 5120, H = 64, K = 8, hd = 128, F = 25600, V = 18944
W_LAYER = 5120 * 64 * 128 + 2 * 5120 * 8 * 128 + 64 * 128 * 5120 \
    + 3 * 5120 * 25600
assert W_LAYER == 487_587_840


def test_weights():
    assert flops.layer_weights(SERVE) == 487_587_840
    assert flops.unembed_weights(SERVE) == 96_993_280


def test_causal_attention_counts_half():
    # 4 H hd per (query, key) pair; a causal 4096 prompt has 4096*4097/2
    assert flops.attention_flops(SERVE, 4096) == 32768 * 8_390_656
    # decode at position 999 sees 1000 keys
    assert flops.attention_flops(SERVE, 1, 999) == 32768 * 1000


def test_train_step_one_layer():
    fwd = 2 * (487_587_840 + 96_993_280) * 4096 + 32768 * 8_390_656
    assert flops.train_flops(TRAIN, 1, 4096) == 3 * fwd == 15_191_500_652_544


def test_prefill_and_decode():
    assert flops.prefill_flops(SERVE, 1) == (2 * 8 * 487_587_840
                                             + 8 * 32768
                                             + 2 * 96_993_280)
    assert flops.decode_flops(SERVE, 0) == (2 * (8 * 487_587_840
                                                 + 96_993_280) + 8 * 32768)


def test_kernel_calls_and_roofline():
    f, b = flops.flash_decode_call(SERVE, [100, 200])
    assert f == 4 * 64 * 128 * 300
    assert b == 2 * 128 * (2 * 8 * 300 + 2 * 64 * 2)       # k, v; q, o
    f, b = flops.flash_attention_call(SERVE, 4096)
    assert f == 32768 * 8_390_656
    assert b == 2 * 128 * (2 * 4096 * 64 + 2 * 4096 * 8)
    assert flops.roofline_seconds(f, b, PEAK) == f / 197e12     # compute
    f, b = flops.flash_decode_call(SERVE, [1000])
    assert flops.roofline_seconds(f, b, PEAK) == b / 819e9      # bandwidth
