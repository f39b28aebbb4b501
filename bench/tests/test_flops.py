"""Each ``bench/flops`` function against a count made by hand at a tiny
size."""
from bench import harness

TINY_LM = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "intermediate_size": 16,
           "num_hidden_layers": 2, "vocab_size": 10}
TINY_MLP = {"n_features": 8, "n_clients": 2, "client_embed": 3,
            "server_embed": 5, "n_classes": 2}


def test_phi3_counts_by_hand():
    f = harness.load_module("flops", "phi3-mini-8l")
    # per layer: q 8x8, k and v 8x4 each (one KV head of size 4), o 8x8,
    # up/gate/down 8x16 each; head 8x10
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert f.matmul_params(TINY_LM) == 2 * per_layer + 80
    # one token against 3 keys: QK and PV, 2 FLOP each, width 8, 2 layers
    assert f.attention_flops_per_token(TINY_LM, 3) == 2 * 2 * 2 * 8 * 3
    # seq 4: contexts 1..4, mean 2.5
    fwd = 2 * (2 * per_layer + 80) + 2 * 2 * 2 * 8 * 2.5
    assert f.forward_flops_per_token(TINY_LM, 4) == fwd
    # q = 2: three forwards plus a backward at two
    assert f.train_flops_per_token(TINY_LM, 4, 2) == 5 * fwd
    # a prompt of 2 and 2 generated: tokens at contexts 1, 2, 3
    w = 2 * (2 * per_layer + 80)
    assert f.serve_flops(TINY_LM, 2, 2) == sum(
        w + 2 * 2 * 2 * 8 * c for c in (1, 2, 3))


def test_paper_mlp_counts_by_hand():
    f = harness.load_module("flops", "paper-mlp-m4")
    # a client sees 4 features -> 3; the server 6 -> 5 -> 2
    assert f.client_forward_flops(TINY_MLP, 7) == 2 * 7 * 4 * 3
    assert f.server_forward_flops(TINY_MLP, 7) == 2 * 7 * (6 * 5 + 5 * 2)
    client, server = 2 * 7 * 12, 2 * 7 * 40
    # q = 1: two client forwards; server forward + backward (3) + 2 lanes
    assert f.round_flops(TINY_MLP, 7, 1) == 2 * client + 5 * server
