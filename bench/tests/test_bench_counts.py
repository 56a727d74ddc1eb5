"""Operation and byte counts against hand-computed shapes, and the peaks
table."""
import pytest

from bench.harness import counts, peaks

QWEN = counts.Shapes(n_layers=28, d_model=1536, n_heads=12, n_kv=2, head_dim=128,
                     d_ff=8960, vocab=151936, gated_mlp=True)


def test_shapes_from_config():
    cfg = {"hidden_size": 1536, "num_attention_heads": 12, "num_key_value_heads": 2,
           "num_hidden_layers": 28, "intermediate_size": 8960, "vocab_size": 151936,
           "hidden_act": "silu"}
    assert counts.Shapes.from_config(cfg) == QWEN


def test_layer_params():
    # q and o: 1536 x 1536 each; k and v: 1536 x 256 each; MLP 3 x 1536 x 8960
    assert counts.layer_matmul_params(QWEN) == (
        2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960)


def test_decode_kernel_needs_live_context_only():
    f, b = counts.decode_kernel(QWEN, [100, 300])
    assert f == 4 * 12 * 128 * 400
    # K and V of 400 positions, 2 KV heads x 128 x 2 bytes each, plus q and
    # out of two requests (12 heads x 128 x 2 bytes each)
    assert b == 400 * 2 * 128 * 2 * 2 + 2 * 12 * 128 * 2 * 2
    assert counts.decode_kernel(QWEN, []) == (0, 0)


def test_prefill_kernel_is_causal():
    f, b = counts.prefill_kernel(QWEN, q_start=256, q_len=4)
    # rows attend 257, 258, 259, 260 keys
    assert f == 4 * 12 * 128 * (257 + 258 + 259 + 260)
    assert b == 260 * 2 * 128 * 2 * 2 + 4 * 12 * 128 * 2 * 2


def test_token_and_chunk_flops_agree():
    s = QWEN
    chunk = counts.prefill_chunk_flops(s, 0, 3, last=True)
    tokens = sum(counts.token_flops(s, c, readout=False) for c in (1, 2, 3))
    assert chunk == tokens + 2 * s.d_model * s.vocab


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
