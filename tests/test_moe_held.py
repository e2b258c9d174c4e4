"""The dropless held-expert layer (models/moe.py ``moe_ffn_dropless``).

A device holds some of the router's experts; the router scores all of them
and the layer adds only its own experts' part. Nothing is dropped, so a
row's output never depends on its batch companions, and the shares of every
device, the shared experts counted once, add up to the uncut layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.models import layers, moe as moe_lib

D = 32


def _layer(e=16, k=4, held=None, start=0, norm=False, seed=0):
    cfg = MoEConfig(num_experts=e, top_k=k, d_ff=24, num_shared_experts=2,
                    norm_topk_prob=norm, held_start=start, num_held=held)
    return cfg, moe_lib.init_moe(jax.random.PRNGKey(seed), D, cfg)


def _share(params, cfg, start, held):
    """The weights one device holds: its experts, the router and the shared
    experts whole."""
    cut = dataclasses.replace(cfg, held_start=start, num_held=held)
    sl = slice(start, start + held)
    return cut, dict(params, wi_gate_edm=params["wi_gate_edm"][sl],
                     wi_up_edm=params["wi_up_edm"][sl], wo_emd=params["wo_emd"][sl])


def test_eight_shares_add_up_to_the_uncut_layer():
    cfg, params = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 10, D))
    whole, aux = moe_lib.moe_ffn_dropless(params, x, cfg)
    shared = layers.mlp(params["shared"], x.reshape(-1, D)).reshape(x.shape)
    total = shared
    slots = 0
    for i in range(8):
        cut, p = _share(params, cfg, 2 * i, 2)
        y, a = moe_lib.moe_ffn_dropless(p, x, cut)
        total = total + (y - shared)
        slots = slots + a["moe_held_slots"]
    np.testing.assert_allclose(total, whole, atol=1e-5)
    # Every routed slot lands on exactly one device's experts.
    np.testing.assert_array_equal(slots, np.full((30,), 4))
    np.testing.assert_array_equal(aux["moe_held_slots"], np.full((30,), 4))


def test_a_row_is_the_same_alone_and_batched():
    cfg, params = _layer(seed=3)
    cut, params = _share(params, cfg, 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 7, D))
    batched, _ = moe_lib.moe_ffn_dropless(params, x, cut)
    for i in range(5):
        alone, _ = moe_lib.moe_ffn_dropless(params, x[i:i + 1], cut)
        np.testing.assert_allclose(alone[0], batched[i], atol=1e-6)
    # The capacity route at the same batch drops tokens; this one never.
    full_cfg, full = _layer(seed=3)
    _, aux = moe_lib.moe_ffn(full, x, dataclasses.replace(
        full_cfg, capacity_factor=0.25))
    assert float(aux["moe_dropped_frac"]) > 0


@pytest.mark.parametrize("norm", [False, True])
def test_weights_follow_norm_topk_prob(norm):
    """Un-normalised gates are the softmax's top-k values; normalised ones
    sum to one over the k (Mixtral's rule, which the capacity route keeps)."""
    cfg, params = _layer(e=4, k=2, norm=norm)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 6, D))
    got, _ = moe_lib.moe_ffn_dropless(params, x, cfg)
    xt = x.reshape(-1, D)
    probs = jax.nn.softmax(xt @ params["router_de"], axis=-1)
    w, idx = jax.lax.top_k(probs, 2)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    want = layers.mlp(params["shared"], xt)
    for e in range(4):
        h = jax.nn.silu(xt @ params["wi_gate_edm"][e]) * (xt @ params["wi_up_edm"][e])
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        want = want + gate[:, None] * (h @ params["wo_emd"][e])
    np.testing.assert_allclose(got.reshape(-1, D), want, atol=1e-5)
    if norm:
        ample = dataclasses.replace(cfg, capacity_factor=100.0)
        cap, _ = moe_lib.moe_ffn(params, x, ample)
        np.testing.assert_allclose(cap, got, atol=1e-5)


def test_the_capacity_route_refuses_a_held_share():
    cfg, params = _layer()
    cut, params = _share(params, cfg, 0, 4)
    with pytest.raises(ValueError, match="held share"):
        moe_lib.moe_ffn(params, jnp.zeros((1, 2, D)), cut)
