"""The work counts against hand arithmetic at a small configuration."""
import pytest

from counts import dense

# d 8, 2 query heads and 1 K/V head of 4, SwiGLU 16, 3 layers, vocabulary 32
C = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
     "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 3,
     "vocab_size": 32, "norm": "rmsnorm", "tie_word_embeddings": False}
LAYER = 8 * (2 * 8 + 2 * 4) + 3 * 8 * 16       # Q, O 8x8; K, V 8x4; SwiGLU 3x8x16


def test_layer_params():
    assert dense.layer_params(C) == LAYER == 576


def test_weight_bytes_count_head_norms_and_no_embedding_table():
    # 3 layers, 7 norm scales of 8 (2 a layer and the final), head 8x32; bf16
    assert dense.weight_bytes(C) == 2 * (3 * 576 + 7 * 8 + 8 * 32)


def test_decode_by_hand():
    w = dense.decode(C, batch=2, context=5)
    attn = 3 * 2 * 8 * 5                          # layers x (QK + PV) x q_dim x context
    assert w["flops"] == 2 * 2 * (3 * 576 + attn + 8 * 32)
    kv = 3 * 2 * 5 * 2 * 4 + 3 * 2 * 2 * 4        # read 5 positions, write 1, K and V
    assert w["bytes"] == dense.weight_bytes(C) + 2 * (2 * 8 + kv)


def test_decode_grows_with_the_valid_context_alone():
    """The count takes the context each row has filled; no allocation
    length enters it, so reading a longer cache than needed cannot raise it."""
    a, b = (dense.decode(C, batch=2, context=n) for n in (5, 6))
    assert b["bytes"] - a["bytes"] == 2 * 3 * 2 * 2 * 4
    assert b["flops"] - a["flops"] == 2 * 2 * 3 * 2 * 8
    with pytest.raises(TypeError):
        dense.decode(C, batch=2, context=5, max_len=1024)


def test_prefill_counts_causal_pairs_and_the_last_position_head():
    w = dense.prefill(C, batch=2, prompt=4)
    pairs = 4 * 5 / 2                             # 10 query-key pairs under the mask
    assert w["flops"] == 2 * 2 * (4 * 3 * 576 + 3 * 2 * 8 * pairs + 8 * 32)
    assert w["bytes"] == dense.weight_bytes(C) + 2 * (2 * 4 * 8 + 3 * 2 * 4 * 2 * 4)


def test_tied_embeddings_read_the_table_once():
    tied = dict(C, tie_word_embeddings=True, norm="layernorm_nonparametric")
    w = dense.decode(tied, batch=2, context=5)
    kv = 3 * 2 * 5 * 2 * 4 + 3 * 2 * 2 * 4
    assert w["bytes"] == 2 * (3 * 576 + 8 * 32) + 2 * kv
