"""The operations and bytes functions against numbers worked out by hand
at both configurations' widths."""
import json
import os

import tiny  # noqa: F401  (puts bench/ on the path)
import work
from arch.moe_transformer import dims

CONFIGS = os.path.join(tiny.BENCH, "configs")


def z_of(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return dims(json.load(f))


def test_granite_widths():
    z = z_of("granite-moe-1b-a400m")
    # one expert: w1, w3 1024x512 and w2 512x1024
    assert work.expert_params(z) == 1_572_864
    # 64 rows x top-8 = 512 pairs, 2 ops per multiply-add
    assert work.grouped_ffn_flops(z, 512) == 1_610_612_736
    # 30 experts' weights + 512 rows in and out, bf16
    assert work.grouped_ffn_bytes(z, 512, 30) == 96_468_992
    # per layer: q,k,v,o 6_291_456 + router 65_536 + 8 experts
    # 25_165_824 + attention over 1 position 4_096 = 31_526_912;
    # x 24 layers + head 2*1024*49155 = 100_669_440
    assert work.token_flops(z, 1) == 857_315_328
    # 2 prompt tokens: body 2 x 756_547_584, causal attention
    # 24 x 4096 x (1 + 2), head once
    assert work.prefill_flops(z, 2) == 1_614_059_520


def test_qwen3_widths():
    z = z_of("qwen3-moe-235b-a22b-l2")
    assert work.expert_params(z) == 18_874_368
    assert work.grouped_ffn_flops(z, 512) == 19_327_352_832
    # 60 experts + 512 rows of 4096
    assert work.grouped_ffn_bytes(z, 512, 60) == 2_273_312_768
    # per layer: q,k,v,o 142_606_336 + router 1_048_576 + 8 experts
    # 301_989_888 + attention 32_768 = 445_677_568; x 2 layers + head
    # 2*4096*151936 = 1_244_659_712
    assert work.token_flops(z, 1) == 2_136_014_848
