"""A configuration brings its own layer stack: a family file that no file of
the benchmark knows (``windowed_global``, three sliding-window GQA layers
then one global layer, each kind with its own rope theta) runs through the
real harness on the CPU, set-up, serving, the correctness check and the
per-layer readers, and its work counts cap each group at its own window."""

import dataclasses
import time

import jax
import pytest

from bench import registry, run, work
from bench.tests import tiny

SEED = 2**31 + 303


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


class NoCompiles:
    n, seconds, cache_hits = 0, 0.0, 0


def _run(cell, *, root, trace=False, seconds=2.0):
    d = jax.devices()[0]
    return run.run_cell(
        cell, seed=SEED, seconds=seconds, trace=trace, root=root,
        device={"platform": d.platform, "kind": d.device_kind, "count": 1},
        t_start=time.perf_counter(), compiles=NoCompiles())


def test_the_cell_finds_its_family_by_the_config(root):
    cell = registry.load_cell(tiny.MIXED_CELL, root)
    assert cell.family.__file__ == str(root / "bench" / "families" / "windowed_global.py")
    cfg = cell.family.model_config(cell.conf)
    assert [(s.window, s.rope_theta) for s in cfg.layer_pattern] == [
        (24, 1e4), (24, 1e4), (24, 1e4), (None, 1e6)]
    assert cfg.n_periods == 1
    # Without its family key the same file is refused by the dense family.
    conf = {k: v for k, v in tiny.MIXED_CONF.items() if k != "family"}
    with pytest.raises(ValueError, match="layer_types"):
        registry.load_family(conf, root).model_config(conf)


def test_mixed_stack_is_correct_when_sound_with_its_readers(root, monkeypatch):
    monkeypatch.setattr(run.work, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    out = _run(registry.load_cell(tiny.MIXED_CELL, root), root=root, trace=True)
    checks = out.result["checks"]
    assert out.result["correct"], checks
    assert checks["tokens_compared"]["value"] >= 8
    m = out.result["metrics"]
    for name in ("batch_occupancy", "prefix_hit_share", "kv_pool_peak_share",
                 "decode_tick_ms", "flush_share"):
        assert name in m and m[name]["value"] >= 0, name


def test_mixed_stack_served_without_its_window_is_not_correct(root, monkeypatch):
    cell = registry.load_cell(tiny.MIXED_CELL, root)
    real = cell.family.model_config

    def unwindowed(conf):
        cfg = real(conf)
        return dataclasses.replace(cfg, layer_pattern=tuple(
            dataclasses.replace(s, window=None) for s in cfg.layer_pattern))

    monkeypatch.setattr(cell.family, "model_config", unwindowed)
    out = _run(cell, root=root)
    checks = out.result["checks"]
    assert checks["tokens_compared"]["value"] >= 8
    assert not out.result["correct"], checks


def test_mixed_stack_counts_each_group_at_its_own_window(root):
    conf = tiny.MIXED_CONF
    stack = registry.load_family(conf, root).work_stack(conf)
    assert [(n, s.window) for n, s in stack.groups] == [(3, 24), (1, None)]
    # 4 query heads over 2 KV heads of 16; d 64, d_ff 128, vocab 256.
    flops_only = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1e30}
    bytes_only = {"bf16_flops": 1e30, "hbm_bytes_per_s": 1.0}
    # Decode, rows of 10 and 100 tokens: sliding layers see 10 + 24, the
    # global one 10 + 100; 4 * Hq * D FLOPs a key.
    ticks = [[10, 100]]
    assert work.decode_least_seconds(stack, ticks, flops_only) == (
        3 * 4 * 4 * 34 * 16 + 4 * 4 * 110 * 16)
    # K and V of each key (2 * Hkv * D * 2 bytes), q and out of each row.
    assert work.decode_least_seconds(stack, ticks, bytes_only) == (
        3 * (2 * 2 * 34 * 16 * 2 + 2 * 2 * 4 * 16 * 2)
        + (2 * 2 * 110 * 16 * 2 + 2 * 2 * 4 * 16 * 2))
    # Extend 4 tokens after 30 cached: each sliding query sees 24 keys (96
    # pairs) and 23 of the prefix; the global one 30 * 4 + 10 = 130 pairs.
    prefills = [(30, 4)]
    assert work.prefill_least_seconds(stack, prefills, flops_only) == (
        3 * 4 * 4 * 16 * 96 + 4 * 4 * 16 * 130)
    assert work.prefill_least_seconds(stack, prefills, bytes_only) == (
        3 * (2 * 4 * 4 * 16 * 2 + 2 * (23 + 4) * 2 * 16 * 2)
        + (2 * 4 * 4 * 16 * 2 + 2 * (30 + 4) * 2 * 16 * 2))
    # Weights a token goes through: 4 layers of q/k/v/o and SwiGLU; the
    # head once for the prefill and once for each decoded row.
    per_tok = 2 * 4 * (64 * 16 * (4 + 2 * 2) + 4 * 16 * 64 + 3 * 64 * 128)
    head = 2 * 64 * 256
    assert work.model_flops(stack, prefills, ticks) == (
        4 * per_tok + head + 3 * 4 * 4 * 16 * 96 + 4 * 4 * 16 * 130
        + 2 * (per_tok + head) + 3 * 4 * 4 * 34 * 16 + 4 * 4 * 110 * 16)


def test_a_family_may_define_its_own_layer_counts(tmp_path):
    # The seam a sparse-expert family uses: a dataclass subclass of
    # ``work.Shape`` in the family's own file, counted through a Stack.
    fam = tmp_path / "bench" / "families"
    fam.mkdir(parents=True)
    (fam / "toy_experts.py").write_text(
        "from __future__ import annotations\n"
        "import dataclasses\n"
        "from bench.work import Shape, Stack\n\n\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class Experts(Shape):\n"
        "    top_k: int = 2\n\n"
        "    def matmul_params(self) -> int:\n"
        "        return self.top_k * 3 * self.d_model * self.d_ff\n\n\n"
        "def work_stack(conf):\n"
        "    dense = Shape(1, 8, 2, 2, 4, 16, 32)\n"
        "    return Stack(32, 8, ((1, dense), (3, Experts(3, 8, 2, 2, 4, 16, 32))))\n")
    stack = registry.load_family({"family": "toy_experts"}, tmp_path).work_stack({})
    assert registry.load_family({"family": "toy_experts"}, tmp_path).work_stack({}) == stack
    dense, experts = (layer for _, layer in stack.groups)
    assert experts.matmul_params() == 2 * 3 * 8 * 16
    # One decoded row of one token: two FLOPs a weight through the four
    # layers, the head (d 8 x vocab 32), attention over one key in each layer.
    assert work.model_flops(stack, [], [[1]]) == (
        2 * (dense.matmul_params() + 3 * experts.matmul_params())
        + 2 * 8 * 32 + 4 * (4 * 2 * 1 * 4))
