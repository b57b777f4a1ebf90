import json
import os

import numpy as np

from perfbench import flops_bytes

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_big_costs_1_28_gflop_a_position():
    # By hand: encoder layer 4 x 1024^2 + 2 x 1024 x 4096 = 12,582,912 weights,
    # decoder layer 8 x 1024^2 + 2 x 1024 x 4096 = 16,777,216, logits 1024 x 37000
    # = 37,888,000; 6 layers each: 75,497,472 + 100,663,296 + 37,888,000 =
    # 214,048,768 weights a source+target position; x 6 (2 forward, 4 backward).
    w = flops_bytes.seq2seq_weight_flops_per_position(model("transformer-big-ende"))
    assert w["src"] == 2 * 75_497_472 and w["tgt"] == 2 * (100_663_296 + 37_888_000)
    assert abs(3 * (w["src"] + w["tgt"]) - 1.284e9) < 0.001e9


def test_train_flops_of_one_pair_by_hand():
    c = model("transformer-big-ende")
    got = flops_bytes.seq2seq_train_flops(c, np.array([10]), np.array([8]))
    weights = 10 * 2 * 75_497_472 + 8 * 2 * 138_551_296
    attention = 6 * 4 * 1024 * (10 * 10 + 8 * 8 / 2 + 8 * 10)
    assert got == 3 * (weights + attention)


def test_starcoder2_3b_holds_30720_bytes_a_token():
    c = model("starcoder2-3b")
    assert flops_bytes.kv_bytes_per_token(c) == 2 * 2 * 128 * 30 * 2 == 30720
    assert flops_bytes.paged_attention_step_bytes(c, 100, 16) == 100 * 16 * 30720
    assert abs(flops_bytes.decoder_lm_params(c) - 3.03e9) < 0.01e9
