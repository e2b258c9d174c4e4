"""work.py counts live and windowed tokens, never the padded grid; the peaks
table refuses a chip it does not know."""

import json

import pytest

from bench import work

MHA = work.Shape(n_layers=4, d_model=4096, n_heads=32, n_kv_heads=32,
                 head_dim=128, d_ff=11008, vocab=102400)
GQA_W = work.Shape(n_layers=8, d_model=4096, n_heads=32, n_kv_heads=8,
                   head_dim=128, d_ff=14336, vocab=32000, window=4096)
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_decode_counts_live_tokens_not_the_page_grid():
    # Two rows of 100 and 3000 live tokens: what the rows hold, whatever
    # the page table's width (4096 tokens here) or the inactive rows.
    flops, nbytes = work.decode_attn_call(MHA, [100, 3000])
    assert flops == 4 * 32 * (100 + 3000) * 128
    kv = 2 * 32 * (100 + 3000) * 128 * 2
    qo = 2 * 2 * 32 * 128 * 2
    assert nbytes == kv + qo
    assert work.decode_attn_call(MHA, []) == (0.0, 0.0)


def test_decode_caps_live_tokens_at_the_window():
    inside, _ = work.decode_attn_call(GQA_W, [4096])
    past, past_bytes = work.decode_attn_call(GQA_W, [10000])
    assert past == inside == 4 * 32 * 4096 * 128
    assert past_bytes == 2 * 8 * 4096 * 128 * 2 + 2 * 32 * 128 * 2


def test_prefill_counts_causal_pairs_of_the_real_tail():
    assert work.prefill_pairs(MHA, 0, 4) == 10
    assert work.prefill_pairs(MHA, 100, 4) == 4 * 100 + 10
    w = work.Shape(1, 64, 4, 2, 16, 128, 256, window=3)
    # Query i sees min(i + 1, 3) keys.
    assert work.prefill_pairs(w, 0, 5) == 1 + 2 + 3 + 3 + 3
    assert work.prefill_pairs(w, 10, 2) == 3 + 3
    f, b = work.prefill_attn_call(w, 10, 2)
    assert f == 4 * 4 * 16 * 6
    # q/out of 2 tail tokens; K/V of the 2 prefix tokens a tail query can
    # see plus the tail's own 2.
    assert b == 2 * 2 * 4 * 16 * 2 + 2 * (2 + 2) * 2 * 16 * 2


def test_least_time_is_the_larger_bound():
    assert work.least_seconds(197e12, 1.0, PEAK) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9, PEAK) == pytest.approx(1.0)
    per_tick = work.least_seconds(*work.decode_attn_call(MHA, [2000] * 16), PEAK)
    assert work.decode_least_seconds(MHA, [[2000] * 16] * 3, PEAK) == pytest.approx(
        3 * 4 * per_tick)


def test_model_flops_count_the_head_once_per_prefill():
    per_tok = 2 * work.layer_matmul_params(MHA) * 4
    head = 2 * 4096 * 102400
    attn = 4 * work.prefill_attn_call(MHA, 16, 8)[0]
    assert work.model_flops(MHA, [(16, 8)], []) == pytest.approx(8 * per_tok + head + attn)
    dec = 4 * work.decode_attn_call(MHA, [30, 40])[0]
    assert work.model_flops(MHA, [], [[30, 40]]) == pytest.approx(
        2 * (per_tok + head) + dec)
    assert work.layer_matmul_params(MHA) == 4096 * 128 * 96 + 4096 * 4096 + 3 * 4096 * 11008


def test_peaks_table_raises_on_an_unknown_kind(tmp_path):
    assert work.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"X": {"bf16_flops": 1.0, "hbm_bytes_per_s": 2.0}}))
    assert work.peaks("X", p)["hbm_bytes_per_s"] == 2.0
    with pytest.raises(KeyError):
        work.peaks("TPU v5 lite", p)
