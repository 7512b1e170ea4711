"""The operations and bytes functions against hand counts."""

import pytest

from benchmarks.lib import costs, device


def test_gpt2_training_flops_by_hand():
    cfg = {"n_embd": 4, "n_layer": 2, "vocab_size": 10, "n_inner": 16}
    # weights a token meets: per layer qkv 4*12 + proj 4*4 + mlp 2*4*16
    per_layer = 48 + 16 + 128
    weights = 2 * per_layer + 10 * 4
    # attention: QK^T and PV, 2 flops * E * (S/2 keys on average) each
    attention = 2 * (2 * 2 * 4 * 8 / 2)
    want = 6 * weights + 3 * attention
    assert costs.gpt2_train_flops_per_token(cfg, 8) == pytest.approx(want)


def test_wenzhong_one_chip_is_about_four_and_a_half_gflop_a_token():
    cfg = {"n_embd": 3072, "n_layer": 5, "vocab_size": 50304,
           "n_inner": 12288}
    got = costs.gpt2_train_flops_per_token(cfg, 1024)
    assert got == pytest.approx(6 * (5 * 12 * 3072 ** 2 + 50304 * 3072)
                                + 3 * 5 * 2 * 3072 * 1024)
    assert 4.3e9 < got < 4.7e9


def test_decode_attention_bytes_by_hand():
    # 3 cached tokens, 2 kv heads of 4, bf16, 5 layers: K and V
    assert costs.decode_attention_bytes(3, 2, 4, 2, 5) == 2 * 3 * 2 * 4 * 2 * 5


def test_an_unknown_device_has_no_peak():
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")
    with pytest.raises(KeyError):
        device.peaks("_source")


def test_a_served_tokens_weight_operations_from_the_leaves():
    """`costs_step`: 2 operations a weight a token; a scanned stack
    counts every layer; an expert table by the picks that land on it;
    the head apart (one row a prompt), the embedding and norms not."""
    from benchmarks.lib import costs_step
    shapes = {
        "model/embed_tokens/embedding": ((1000, 64), "bfloat16"),
        "lm_head/kernel": ((64, 1000), "bfloat16"),
        "model/norm/scale": ((64,), "bfloat16"),
        "model/layers/layer/mlp/up_proj/kernel": ((3, 64, 256), "bfloat16"),
        "model/layers_0/self_attn/q_proj/kernel": ((64, 64), "bfloat16"),
        "model/layers_0/mlp/router/kernel": ((64, 16), "bfloat16"),
        "model/layers_0/mlp/experts_gate": ((8, 64, 32), "bfloat16"),
    }
    # all 16 of the router's outputs held nowhere else: 8 tables here,
    # top-4 of 16 picks: a token passes through 2 of them on average
    body, head = costs_step.weight_flops_per_token(
        shapes, {"num_experts_per_tok": 4, "router_width": 16})
    assert head == 2 * 64 * 1000
    assert body == 2 * (3 * 64 * 256 + 64 * 64 + 64 * 16 + 2 * 64 * 32)
    # every expert held: top-4 of the 8 there are
    body, _ = costs_step.weight_flops_per_token(
        shapes, {"num_experts_per_tok": 4, "num_experts": 8})
    assert body == 2 * (3 * 64 * 256 + 64 * 64 + 64 * 16 + 4 * 64 * 32)


def test_the_steps_share_of_peak_credits_the_head_to_one_row_a_prompt():
    from benchmarks.lib import manifest
    from benchmarks.tests import tiny
    config = tiny.mistral()
    family = manifest.family(config)
    import importlib

    from benchmarks.lib import costs_step
    shapes = importlib.import_module(family.REFERENCE).param_shapes(
        family.reference_config(config))
    body, head = costs_step.weight_flops_per_token(shapes, config)
    # one request: 100 prompt tokens credited at its first token, which
    # with two more output tokens falls in the 2 s window
    obs = {"config": config, "chips": 1, "window": (10.0, 12.0),
           "peaks": {"bf16_flops_per_s": 1e9},
           "records": [{"prompt_len": 100, "token_times": [10.5, 10.6, 11.0],
                        "due": 10.0, "failed": False}]}
    got = manifest.reader("step_mfu.serve")(obs)
    assert got == pytest.approx(100 * (103 * body + 3 * head) / 2.0 / 1e9)
    assert manifest.reader("step_mfu.serve")({"config": config}) is None
