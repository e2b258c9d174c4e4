"""The deepseek_v2 family: latent attention and held experts through the real
harness on the CPU. A tiny configuration of the family's own keys is served
``correct`` against its float32 reference, with the held-expert counter read;
it is not correct when the program's latent values or one held expert are
corrupted; the family refuses every mechanism it does not compute; its work
counts follow the absorbed form."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import registry, run, work
from bench.tests import tiny

SEED = 2**33 + 17
CELL = "tiny-v2.session"

#: DeepSeek-V2's keys at tiny widths: 4 heads of 16 + 8, a 32-wide latent,
#: one dense layer then two expert layers, 4 of 8 experts held.
CONF = {
    "name": "tiny-v2", "family": "deepseek_v2",
    "architectures": ["DeepseekV2ForCausalLM"], "model_type": "deepseek_v2",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
    "topk_group": 1, "vocab_size": 256, "max_position_embeddings": 163840,
    "rope_theta": 10000, "rms_norm_eps": 1e-06, "attention_bias": False,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "torch_dtype": "bfloat16",
    "expert_parallel": {"chips": 2, "router_experts": 8, "held_start": 4},
    "engine": {"kv_layout": "paged", "page_size": 16, "steps_per_sync": 4,
               "batch_prefills": False},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("checkout"))
    (root / "bench" / "configs" / "tiny-v2.json").write_text(json.dumps(CONF))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(json.dumps(tiny.LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "tiny-v2",
                              "traffic": "tiny-session", "chips": 1, "why": "tiny"})
    for m in spec["per_layer"]:
        if m["name"] in ("latent_decode_roofline", "latent_prefill_roofline",
                         "held_expert_slots_per_tick"):
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root


class NoCompiles:
    n, seconds, cache_hits = 0, 0.0, 0


def _run(cell, *, root, trace=False, seconds=2.0):
    d = jax.devices()[0]
    return run.run_cell(
        cell, seed=SEED, seconds=seconds, trace=trace, root=root,
        device={"platform": d.platform, "kind": d.device_kind, "count": 1},
        t_start=time.perf_counter(), compiles=NoCompiles())


def test_the_family_is_correct_through_the_harness(root, monkeypatch):
    monkeypatch.setattr(run.work, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    cell = registry.load_cell(CELL, root)
    assert [m["name"] for m in cell.per_layer] == [
        "latent_decode_roofline", "latent_prefill_roofline",
        "held_expert_slots_per_tick"]
    out = _run(cell, root=root, trace=True)
    checks = out.result["checks"]
    assert out.result["correct"], checks
    assert checks["tokens_compared"]["value"] >= 8
    # 3 routed slots a token over 8 experts, 4 of them held here.
    held = out.result["metrics"]["held_expert_slots_per_tick"]["value"]
    assert 0 < held <= 3 * tiny.SESSION["max_batch"]


def _corrupt_values(monkeypatch):
    """The program's latent decode takes its values one column late."""
    from repro.kernels import ops, ref as ref_mod

    def shifted(q, pages, pt, lengths, *, v_dim, scale, plan):
        kv = ref_mod.gather_pages(pages, pt)
        return ref_mod.decode_attention(q, kv, kv[..., 1:v_dim + 1], lengths,
                                        scale=scale)

    monkeypatch.setattr(ops, "paged_latent_decode", shifted)


def _corrupt_one_expert(monkeypatch):
    """The program's first held expert returns its output sixteen times
    over, as a wrong weight scale would. Zeroed, it flipped too few greedy
    picks of the tiny cell's short window to be caught every run (a gap of
    0.16 against the limit 0.1 alone, 0.017 once in a loaded suite)."""
    from repro.models import moe as moe_lib

    real = moe_lib.moe_ffn_dropless

    def broken(params, x, cfg):
        params = dict(params, wo_emd=params["wo_emd"].at[0].multiply(16.0))
        return real(params, x, cfg)

    monkeypatch.setattr(moe_lib, "moe_ffn_dropless", broken)


@pytest.mark.parametrize("fault", [_corrupt_values, _corrupt_one_expert],
                         ids=["latent_values", "held_expert"])
def test_a_corrupted_program_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(registry.load_cell(CELL, root), root=root)
    checks = out.result["checks"]
    assert checks["tokens_compared"]["value"] >= 8
    assert not out.result["correct"], checks


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
    ("topk_method", "group_limited_greedy"), ("n_group", 8),
    ("num_nextn_predict_layers", 1),
    ("rope_scaling", {"type": "linear", "factor": 4.0}),
])
def test_the_family_refuses_what_it_does_not_compute(root, key, value):
    conf = dict(CONF, **{key: value})
    with pytest.raises(ValueError, match=key if key != "rope_scaling" else "rope"):
        registry.load_family(conf, root).model_config(conf)


def test_the_published_file_loads_at_its_widths():
    conf = json.loads((tiny.REPO / "bench" / "configs" / "deepseek-v2-lite.json")
                      .read_text())
    cfg = registry.load_family(conf).model_config(conf)
    assert (cfg.d_model, cfg.n_heads, cfg.mla.kv_lora_rank,
            cfg.mla.qk_head_dim, cfg.mla.v_head_dim) == (2048, 16, 512, 192, 128)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k,
            cfg.moe.num_shared_experts) == (64, 8, 6, 2)
    assert len(cfg.lead_pattern) == 1 and cfg.n_periods == 7
    assert cfg.param_count() == 836_276_224


def test_work_counts_follow_the_absorbed_form():
    conf = json.loads((tiny.REPO / "bench" / "configs" / "deepseek-v2-lite.json")
                      .read_text())
    stack = registry.load_family(conf).work_stack(conf)
    (n_dense, dense), (n_moe, moe) = stack.groups
    assert (n_dense, n_moe) == (1, 7)
    flops, nbytes = dense.decode_attn_call([4096])
    assert flops == 2 * 16 * (576 + 512) * 4096
    assert nbytes == 4096 * 1152 + 16 * (576 + 512) * 2
    # Experts: 6 routed slots a token, 8 of 64 held, plus 2 shared.
    assert moe.matmul_params() - dense.attn_params == (
        (6 * 8 / 64 + 2) * 3 * 2048 * 1408 + 2048 * 64)
    assert dense.matmul_params() - dense.attn_params == 3 * 2048 * 10944
    # Least decode time: one read of each live row, at any peak.
    peak = {"bf16_flops": 1e15, "hbm_bytes_per_s": 1e9}
    got = work.decode_least_seconds(stack, [[100, 200]], peak)
    rows = 300 * 1152 + 2 * 16 * 1088 * 2
    assert got == pytest.approx(8 * rows / 1e9)
    # Extend of 3 tokens over 5: 3 * 5 + 6 causal pairs, each row read once.
    flops, nbytes = moe.prefill_attn_call(5, 3)
    assert flops == 2 * 16 * 1088 * 21
    assert nbytes == 8 * 1152 + 3 * 16 * 1088 * 2
    got = work.prefill_least_seconds(stack, [(5, 3)], {"bf16_flops": 1.0,
                                                        "hbm_bytes_per_s": 1e30})
    assert got == pytest.approx(8 * flops)


def test_reference_yarn_matches_the_published_formula():
    fam = registry.load_family(CONF)
    inv = fam.yarn_inv_freq(64, 1e4, CONF["rope_scaling"])
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    # low = floor(10.47) = 10, high = ceil(22.51) = 23: dims below low keep
    # the original frequency, from high on they are divided by the factor
    # 40, and between them a linear ramp blends the two.
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, extra * (1 - ramp) + extra / 40 * ramp)
    np.testing.assert_allclose(inv[:10], extra[:10])
    np.testing.assert_allclose(inv[23:], extra[23:] / 40)
    assert fam.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)


def test_tiny_program_and_reference_agree_at_float32():
    """The program in float32 against the family's reference: the absorbed
    latent path, the held share and the dense first layer all match."""
    from bench import model, reference
    from repro.models import transformer

    conf = dict(CONF, torch_dtype="float32")
    fam = registry.load_family(conf)
    cfg = fam.model_config(conf)
    params = model.make_params(cfg, 5)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (40,), 1, 256),
                      np.int32)
    lg, _ = transformer.prefill(params, cfg, jnp.asarray(toks[None]), cache_len=48)
    want = reference.logits(params, conf, toks, np.array([39]), family=fam)
    np.testing.assert_allclose(np.asarray(lg), want, atol=2e-4, rtol=2e-4)
