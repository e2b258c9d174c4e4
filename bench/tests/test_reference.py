"""The plain reference agrees with the program's model at smoke size, for a
GQA model with a sliding window and for an MHA model."""

import numpy as np
import pytest

from bench import model, reference

BASE = {
    "name": "smoke", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "max_position_embeddings": 4096, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
}


@pytest.mark.parametrize("kv_heads,window", [(2, 24), (4, None)],
                         ids=["gqa-window", "mha"])
def test_reference_matches_the_program_forward(kv_heads, window):
    import jax

    from repro.models import transformer

    conf = dict(BASE, num_key_value_heads=kv_heads, sliding_window=window)
    cfg = model.model_config(conf)
    params = model.make_params(cfg, 2**31 + 7)
    tokens = np.random.default_rng(0).integers(1, 128, size=40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = transformer.forward(params, cfg, tokens[None], remat=False)
    want = np.asarray(want)[0]
    pos = np.arange(40)
    got = reference.logits(params, conf, tokens, pos)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_params_follow_the_seed():
    conf = dict(BASE, num_key_value_heads=2, sliding_window=None)
    cfg = model.model_config(conf)
    a = model.make_params(cfg, 5)
    b = model.make_params(cfg, 5)
    c = model.make_params(cfg, 6)
    ea, eb, ec = (np.asarray(p["embed"]["table_vd"]) for p in (a, b, c))
    assert np.array_equal(ea, eb) and not np.array_equal(ea, ec)
    gain = np.asarray(a["layers"][0]["ln1"]["scale_r"])
    assert gain.shape == (2, 64) and 0.05 < gain.std() < 0.2


def test_widest_gap():
    lg = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, 0.25]])
    assert reference.widest_gap(lg, np.array([1, 0])) == 0.0
    assert reference.widest_gap(lg, np.array([2, 1])) == pytest.approx(1.0)
