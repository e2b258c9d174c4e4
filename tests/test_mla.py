"""Latent attention (models/mla.py) against its expanded float32 form.

A tiny DeepSeek-V2-shaped stack (latent attention on every layer, a leading
dense layer, then dropless expert layers) is prefilled into the paged latent
pool, extended over a prefix two sequences share in place, and decoded; at
every step the logits equal those of the expanded training forward. The
Pallas latent kernels (interpret mode) and the gather oracle are both held
to it, and YaRN's frequencies and scale equal their published values.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (LatentModelConfig, LayerSpec, MLAConfig,
                                MoEConfig, YarnConfig)
from repro.models import layers, mla as mla_lib, transformer
from repro.serving.backends import PagedBackend

YARN = YarnConfig(factor=40, original_max_position=4096, beta_fast=32,
                  beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
PS = 8


def tiny_cfg(impl: str = "auto") -> LatentModelConfig:
    mla = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, yarn=YARN)
    return LatentModelConfig(
        name="tiny-mla", d_model=64, n_layers=3, n_heads=4, n_kv_heads=1,
        head_dim=mla_lib.pool_width(mla), d_ff=96, vocab=256,
        lead_pattern=(LayerSpec(ffn="mlp"),), layer_pattern=(LayerSpec(ffn="moe"),),
        # Ample capacity: the training forward then drops nothing either.
        moe=MoEConfig(num_experts=8, top_k=3, d_ff=32, num_shared_experts=2,
                      capacity_factor=100.0, norm_topk_prob=False),
        mla=mla, tie_embeddings=False, compute_dtype="float32",
        max_seq_len=4096, attn_impl=impl,
    )


def _forward(params, cfg, tokens):
    logits, _ = transformer.forward(params, cfg, tokens, remat=False)
    return logits


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_paged_prefill_extend_and_decode_match_the_expanded_forward(impl):
    cfg = tiny_cfg(impl)
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    b, prefix, tail, extra = 2, 24, 13, 3
    shared = jax.random.randint(jax.random.PRNGKey(1), (prefix,), 0, 256)
    rest = jax.random.randint(jax.random.PRNGKey(2), (b, tail + extra), 0, 256)
    tokens = jnp.concatenate([jnp.broadcast_to(shared, (b, prefix)), rest], 1)
    want = _forward(params, cfg, tokens)

    caches = transformer.init_paged_caches(params, cfg, 24, PS)
    # The shared prefix, prefilled once into pages 1-3.
    lg, tails = transformer.prefill(params, cfg, shared[None], cache_len=prefix)
    np.testing.assert_allclose(lg[0], want[0, prefix - 1], atol=1e-4)
    caches = PagedBackend._scatter_tail(caches, tails, jnp.asarray([[1, 2, 3]]))
    # Both sequences extend it in place; their tails go to pages of their own.
    pt = np.zeros((b, 4), np.int32)
    pt[:, :3] = [1, 2, 3]
    lg, tails = transformer.prefill(
        params, cfg, rest[:, :tail], cache_len=16,
        prefix_caches=caches, page_table=jnp.asarray(pt[:, :3]),
        prefix_len=jnp.full((b,), prefix, jnp.int32),
        last_positions=jnp.full((b,), tail - 1, jnp.int32))
    np.testing.assert_allclose(lg, want[:, prefix + tail - 1], atol=1e-4)
    own = np.array([[4, 5], [6, 7]], np.int32)
    caches = PagedBackend._scatter_tail(caches, tails, jnp.asarray(own))
    table = np.zeros((b, 6), np.int32)
    table[:, :3] = [1, 2, 3]
    table[:, 3:5] = own
    lengths = jnp.full((b,), prefix + tail, jnp.int32)
    for t in range(extra):
        lengths = lengths + 1
        lg, caches = transformer.decode_step(
            params, cfg, rest[:, tail + t], caches, lengths,
            page_table=jnp.asarray(table))
        np.testing.assert_allclose(lg, want[:, prefix + tail + t], atol=1e-4)


def test_the_pool_holds_one_lane_padded_latent_row_a_token():
    cfg = tiny_cfg()
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    caches = transformer.init_paged_caches(params, cfg, 10, PS)
    assert caches["lead"][0]["attn"]["latent_pages"].shape == (1, 10, PS, 128)
    assert caches["scanned"][0]["attn"]["latent_pages"].shape == (2, 1, 10, PS, 128)
    assert mla_lib.latent_bytes_per_token(cfg) == (40 * 4, 128 * 4)
    with pytest.raises(ValueError, match="paged latent pool"):
        transformer.init_caches(params, cfg, 2, 32)


def test_yarn_frequencies_and_scale_equal_the_published_values():
    inv, cos_scale, scale = mla_lib.rope_tables(
        MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, yarn=YARN))
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(1e4)))
    assert (low, high) == (10, 23)
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, extra * (1 - ramp) + extra / 40 * ramp,
                               rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert scale == pytest.approx(192 ** -0.5 * m * m)
    assert cos_scale == 1.0
    assert layers.yarn_mscale(40, 0.707) == pytest.approx(m)


def test_latent_decode_kernel_matches_the_gather_oracle_with_splits():
    from repro.kernels.paged_decode_attention import paged_latent_decode
    from repro.kernels import ref as ref_mod

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    pool = jax.random.normal(ks[0], (1, 40, PS, 128)).astype(jnp.bfloat16)
    pt = jax.random.permutation(ks[1], jnp.arange(1, 40))[:18].reshape(3, 6)
    lengths = jnp.array([5, 41, 0], jnp.int32)
    q = jax.random.normal(ks[2], (3, 4, 128)).astype(jnp.bfloat16)
    kv = ref_mod.gather_pages(pool, pt)
    want = ref_mod.decode_attention(q, kv, kv[..., :96], lengths, scale=0.2)
    for splits, ppb in [(1, 2), (1, 4), (3, 2)]:
        got = paged_latent_decode(q, pool, pt.astype(jnp.int32), lengths,
                                  v_dim=96, scale=0.2, num_splits=splits,
                                  pages_per_block=ppb, interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-2)
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0   # a length-0 row


def test_latent_prefill_kernel_over_cells_and_blocks():
    """Cells of several positions and blocks of several pages: every split
    of the walk gives the oracle's answer, fresh and over a prefix."""
    from repro.kernels.paged_prefill_attention import paged_latent_prefill
    from repro.kernels import ref as ref_mod

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    b, st, h, w, v = 3, 20, 4, 128, 96
    pool = jax.random.normal(ks[0], (1, 40, PS, w)).astype(jnp.bfloat16)
    pt = jax.random.permutation(ks[1], jnp.arange(1, 40))[:18].reshape(b, 6)
    q = jax.random.normal(ks[2], (b, st, h, w)).astype(jnp.bfloat16)
    tail = jax.random.normal(ks[3], (b, st, w)).astype(jnp.bfloat16)
    tlen = jnp.array([20, 7, 1], jnp.int32)
    for plen in (jnp.array([0, 13, 48], jnp.int32), jnp.zeros((b,), jnp.int32)):
        want = ref_mod.paged_prefill_attention(
            q.transpose(0, 2, 1, 3), pool, pool[..., :v], pt.astype(jnp.int32),
            tail[:, None], tail[:, None, :, :v], plen, tlen, scale=0.2,
        ).transpose(0, 2, 1, 3)
        for rows, block in [(32, 8), (64, 16), (None, None)]:
            got = paged_latent_prefill(
                q, pool, pt.astype(jnp.int32), tail, plen, tlen, v_dim=v,
                scale=0.2, rows=rows, block_tokens=block, interpret=True)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32), atol=2e-2)


def test_latent_plans_keep_one_pool_copy_a_block():
    from repro.kernels import plan as plan_lib

    both = plan_lib.paged_decode_vmem_bytes(1024, 640, 2)
    one = plan_lib.paged_decode_vmem_bytes(1024, 640, 2, latent=True)
    assert one == 1024 * 640 * 2 * 2 and one < both / 3
    cfg = dataclasses.replace(tiny_cfg(), head_dim=128)
    p = plan_lib.plan_for_config(cfg, (4, 4, 1, 1, 64, 128), phase=plan_lib.DECODE,
                                 kv_layout=plan_lib.PAGED, page_size=PS,
                                 latent=True, backend="cpu")
    assert p.impl == "xla" and p.pages_per_block >= 1
    p = plan_lib.plan_for_config(cfg, (4, 4, 1, 16, 80, 128), phase=plan_lib.EXTEND,
                                 kv_layout=plan_lib.PAGED, page_size=PS,
                                 prefix_pages=8, latent=True, backend="cpu")
    assert p.impl == "xla"


def test_a_rows_logits_are_the_same_alone_and_batched():
    """Dropless experts: no row's logits depend on its batch companions."""
    cfg = dataclasses.replace(tiny_cfg(), moe=dataclasses.replace(
        tiny_cfg().moe, capacity_factor=0.25, num_held=4, held_start=2))
    params = transformer.init_model(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 20), 0, 256)
    batched, _ = transformer.prefill(params, cfg, tokens, cache_len=32)
    for i in range(4):
        alone, _ = transformer.prefill(params, cfg, tokens[i:i + 1], cache_len=32)
        np.testing.assert_allclose(alone[0], batched[i], atol=1e-5)


def test_the_engine_serves_it_paged_and_counts_held_slots():
    """Through ``LLMEngine(kv_layout="paged")``: the decode and flush spans
    carry the held-expert slots, and ``stats()`` the latent row's bytes and
    the held range."""
    from repro.obs import Telemetry
    from repro.serving import LLMEngine, Request, SamplingParams

    cfg = dataclasses.replace(tiny_cfg(), compute_dtype="bfloat16", moe=dataclasses.replace(
        tiny_cfg().moe, held_start=2, num_held=4))
    params = transformer.init_model(jax.random.PRNGKey(9), cfg)
    tel = Telemetry.create()
    eng = LLMEngine(cfg, params, kv_layout="paged", max_batch=4, num_pages=64,
                    page_size=PS, max_pages_per_seq=8, prompt_buckets=(16, 32),
                    steps_per_sync=4, telemetry=tel)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(1, 256, 12 + 5 * i).astype(np.int32),
                    SamplingParams(max_tokens=6)) for i in range(3)]
    outs = eng.generate(reqs)
    assert sorted(len(o.tokens) for o in outs) == [6, 6, 6]
    st = eng.stats()
    assert st.latent_bytes_per_token == 40 * 2 and st.held_experts == (2, 6)
    decode = [s for s in tel.tracer.spans if s.name == "decode"]
    held = [h for s in decode for h in s.args["held_slots"]]
    assert decode[0].args["moe_layers"] == 2
    # Per live tick, over two layers: at most 3 slots a row land here.
    assert sum(held) > 0 and all(0 <= h <= 2 * 3 * 3 for h in held)
    flush = [s for s in tel.tracer.spans if s.name == "flush"]
    assert flush and all(0 < s.args["held_slots"] <= 2 * 3 * 32 for s in flush)
    eng.close()
