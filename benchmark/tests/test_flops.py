"""benchmark/flops.py against counts worked by hand."""

import json
import os

from benchmark import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_one_bert_layer_by_hand():
    # H=1024, I=4096, one token over 128 keys:
    # QKV 2*1024*3072 = 6,291,456; out 2*1024*1024 = 2,097,152;
    # MLP 2*2*1024*4096 = 16,777,216; QK^T + PV 2*2*128*1024 = 524,288
    assert flops.encoder_layer_flops_per_token(1024, 4096, 128) == (
        6_291_456 + 2_097_152 + 16_777_216 + 524_288
    )


def test_bert_step_by_hand():
    c = cfg("bert-large-uncased")
    per_seq = 128 * 24 * 25_690_112
    heads = 20 * (2 * 1024 * 1024 + 2 * 1024 * 30522) + 2 * 1024 * 1024 + 4 * 1024
    assert flops.bert_forward_flops_per_seq(c, 128, 20) == per_seq + heads
    # forward + backward = 3x forward, per input token; ~6·N·T less the
    # unmasked positions' share of the decoder
    per_token = flops.bert_train_flops_per_token(c, 128, 20)
    assert per_token == 3 * (per_seq + heads) / 128
    assert 1.8e9 < per_token < 2.0e9


def test_one_gpt2_layer_by_hand():
    c = cfg("gpt2-large")
    # H=1280, I=5120, decode token over 300 keys, one of 36 layers:
    # 8*1280^2 = 13,107,200; 4*1280*5120 = 26,214,400; 4*300*1280 = 1,536,000
    layer = 13_107_200 + 26_214_400 + 1_536_000
    assert flops.encoder_layer_flops_per_token(1280, 5120, 300) == layer
    assert flops.gpt_token_flops(c, 300, True) == 36 * layer + 2 * 1280 * 50257
    assert flops.gpt_token_flops(c, 300, False) == 36 * layer


def test_prefill_is_the_sum_of_its_tokens():
    c = cfg("gpt2-large")
    n = 37
    by_token = sum(flops.gpt_token_flops(c, p + 1, False) for p in range(n))
    assert abs(flops.gpt_prefill_flops(c, n)
               - (by_token + 2 * 1280 * 50257)) < 1.0


def test_bytes_by_hand():
    c = cfg("gpt2-large")
    # 774,030,080 parameters in all; the position table (1024*1280) is not
    # streamed by a decode step
    assert flops.gpt_weight_bytes(c, 1) == 774_030_080 - 1024 * 1280
    assert flops.gpt_kv_bytes_per_token(c, 2) == 2 * 36 * 1280 * 2
    assert flops.layer_norm_bytes(16384, 1024, 2, 2, False) == 16384 * 1024 * 4
