"""The dense decoder family: both configurations read exactly as before the
family seam, and a file naming a mechanism the family lacks is refused when
its cell is loaded.

``data/dense_golden.json`` and ``data/tiny_logits.npz`` were recorded from
``bench/model.py``, ``bench/work.py`` and ``bench/reference.py`` as they
stood before the seam, when the dense decoder was the harness's only model;
every number is compared exactly."""

import dataclasses
import json
import pathlib

import numpy as np
import pytest

from bench import model, reference, registry, work
from bench.tests import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"
GOLDEN = json.loads((DATA / "dense_golden.json").read_text())
CONFIGS = tiny.REPO / "bench" / "configs"


def _conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(GOLDEN["configs"]))
def test_model_config_is_as_before(name):
    cfg = model.model_config(_conf(name))
    got = json.loads(json.dumps(dataclasses.asdict(cfg)))
    assert got == GOLDEN["configs"][name]["model_config"]


@pytest.mark.parametrize("name", sorted(GOLDEN["configs"]))
def test_work_counts_are_as_before(name):
    want = GOLDEN["configs"][name]
    stack = model.work_shape(_conf(name))
    ticks = GOLDEN["ticks"]
    prefills = [tuple(p) for p in GOLDEN["prefills"]]
    peak = work.peaks("TPU v5 lite")
    assert work.decode_least_seconds(stack, ticks, peak) == want["decode_least_seconds"]
    assert work.prefill_least_seconds(stack, prefills, peak) == want["prefill_least_seconds"]
    assert work.model_flops(stack, prefills, ticks) == want["model_flops"]


def test_reference_logits_are_as_before():
    rec = np.load(DATA / "tiny_logits.npz")
    params = model.make_params(model.model_config(tiny.CONF), int(rec["seed"]))
    for quantized, key in ((False, "fp32"), (True, "float8")):
        got = reference.logits(params, tiny.CONF, rec["tokens"], rec["score_pos"],
                               quantized=quantized)
        np.testing.assert_array_equal(got, rec[key])


REFUSED = [
    ("num_experts", 8), ("n_routed_experts", 64), ("num_local_experts", 8),
    ("layer_types", ["sliding_attention", "full_attention"]),
    ("mlp_layer_types", ["dense", "sparse"]), ("kv_lora_rank", 512),
    ("q_lora_rank", 1536), ("rope_parameters", {"rope_type": "default"}),
    ("partial_rotary_factor", 0.5), ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("tie_word_embeddings", True), ("architectures", ["DeepseekV2ForCausalLM"]),
    ("hidden_act", "gelu_pytorch_tanh"), ("attention_bias", True),
]


@pytest.mark.parametrize("key,value", REFUSED, ids=[k for k, _ in REFUSED])
def test_dense_family_refuses_what_it_does_not_compute(tmp_path, key, value):
    root = tiny.make_root(tmp_path)
    path = root / "bench" / "configs" / "tiny.json"
    path.write_text(json.dumps(dict(tiny.CONF, **{key: value})))
    with pytest.raises(ValueError, match=key):
        registry.load_cell("tiny.session", root)


@pytest.mark.parametrize("name", sorted(GOLDEN["configs"]))
def test_the_repository_configs_load_as_dense_decoders(name):
    conf = _conf(name)
    assert "family" not in conf
    fam = registry.load_family(conf)
    assert fam.__file__ == str(tiny.REPO / "bench" / "families" / "dense_decoder.py")
    assert model.model_config(conf).name == name
