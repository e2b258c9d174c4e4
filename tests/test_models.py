"""Per-architecture smoke tests: reduced configs of every assigned family.

Each arch: forward (train) produces finite logits of the right shape; a
train step reduces loss; prefill+decode match the full forward. Covers all
10 assigned architectures from the public pool.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.models import transformer
from repro.optim.adamw import AdamWConfig
from repro.training.train_step import TrainConfig, init_train_state, make_train_step

ARCHS = list(registry.ARCH_IDS)


def make_batch(cfg, b, s, seed=0):
    key = jax.random.PRNGKey(seed)
    shape = (b, s) if cfg.num_codebooks == 1 else (b, s, cfg.num_codebooks)
    tokens = jax.random.randint(key, shape, 0, cfg.vocab)
    batch = {
        "tokens": tokens,
        "targets": jnp.roll(tokens, -1, axis=1),
        "mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.vision_tokens:
        batch["image_embeds"] = jax.random.normal(
            key, (b, cfg.vision_tokens, cfg.vision_dim)
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch):
    cfg = registry.get_smoke_config(arch)
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, 2, 64)
    logits, aux = transformer.forward(
        params, cfg, batch["tokens"],
        image_embeds=batch.get("image_embeds"), remat=False,
    )
    want = (2, 64, cfg.vocab) if cfg.num_codebooks == 1 else (
        2, 64, cfg.num_codebooks, cfg.vocab)
    assert logits.shape == want
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_reduces_loss(arch):
    cfg = registry.get_smoke_config(arch)
    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=2e-3, warmup_steps=1, total_steps=10),
        microbatches=1,
    )
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    batch = make_batch(cfg, 2, 32)
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)  # same batch: loss must fall
        losses.append(float(metrics["loss"]))
    assert all(jnp.isfinite(jnp.asarray(losses)))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma2-2b", "mamba2-1.3b",
                                  "hymba-1.5b", "mixtral-8x7b", "musicgen-medium",
                                  "llama-3.2-vision-11b"])
def test_prefill_decode_matches_forward(arch):
    cfg = registry.get_smoke_config(arch)
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    b, s, extra = 2, 48, 3
    batch = make_batch(cfg, b, s + extra, seed=1)
    tokens = batch["tokens"]
    img = batch.get("image_embeds")
    full, _ = transformer.forward(params, cfg, tokens, image_embeds=img, remat=False)
    lg, caches = transformer.prefill(
        params, cfg, tokens[:, :s], cache_len=s + extra, image_embeds=img
    )
    assert jnp.max(jnp.abs(lg - full[:, s - 1])) < 1e-3
    lengths = jnp.full((b,), s, jnp.int32)
    for t in range(extra):
        lengths = lengths + 1
        lg, caches = transformer.decode_step(
            params, cfg, tokens[:, s + t], caches, lengths
        )
        assert jnp.max(jnp.abs(lg - full[:, s + t])) < 1e-3


def test_scan_vs_unrolled_stack():
    """scan-over-periods == the same stack with the scan unrolled."""
    cfg = registry.get_smoke_config("gemma2-2b")
    import dataclasses
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, 1, 32)
    l1, _ = transformer.forward(params, cfg, batch["tokens"], remat=False)
    cfg2 = dataclasses.replace(cfg, scan_unroll=cfg.n_periods)
    l2, _ = transformer.forward(params, cfg2, batch["tokens"], remat=False)
    assert jnp.max(jnp.abs(l1 - l2)) < 1e-4


def test_param_counts_match_published():
    expected = {
        "llama3-8b": 8.0e9, "llama3-405b": 405e9, "mixtral-8x7b": 46.7e9,
        "mamba2-1.3b": 1.3e9, "gemma2-2b": 2.6e9,
    }
    for arch, want in expected.items():
        got = registry.get_config(arch).param_count()
        assert abs(got - want) / want < 0.06, (arch, got)


@pytest.mark.parametrize("arch", ARCHS)
def test_jitted_init_matches_eager(arch):
    """The jitted init_model is bitwise the op-by-op init, per smoke arch."""
    cfg = registry.get_smoke_config(arch)
    key = jax.random.PRNGKey(3)
    jitted = transformer.init_model(key, cfg)
    eager = transformer.init_model_eager(key, cfg)
    assert jax.tree.structure(jitted) == jax.tree.structure(eager)
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.array_equal(a, b))
