"""The harness end to end on the CPU at a tiny size: cells found by name,
``correct`` true for the program as it is and false with the timed path
broken underneath, the float8 control failing its limit, and a traced run
reporting the metrics its spans and counters give."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import check, registry, run
from bench.tests import tiny

SEED = 2**31 + 101


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


class NoCompiles:
    n, seconds, cache_hits = 0, 0.0, 0


def _run(root, cell, *, seconds=2.0, trace=False, control=False, seed=SEED):
    c = registry.load_cell(cell, root)
    d = jax.devices()[0]
    return run.run_cell(
        c, seed=seed, seconds=seconds, trace=trace, root=root,
        device={"platform": d.platform, "kind": d.device_kind, "count": 1},
        t_start=time.perf_counter(), compiles=NoCompiles(), control=control)


def test_made_up_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = root / "bench"
    (bench / "configs" / "toy.json").write_text(json.dumps(dict(tiny.CONF, name="toy")))
    (bench / "traffic" / "toy-mix.json").write_text(json.dumps(dict(tiny.CHAT, rate=9.0)))
    (bench / "limits" / "toy.cell.json").write_text(json.dumps(tiny.LIMITS))
    (bench / "metrics" / "toy_metric.py").write_text("def read(ctx):\n    return 42.0\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toy-mix", "chips": 1, "why": "toy"})
    spec["per_layer"].append({"name": "toy_metric", "unit": "ms", "better": "lower",
                              "source": "program_span", "layer": "engine",
                              "moves": "tpot_p90_ms", "workloads": ["toy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = registry.load_cell("toy.cell", root)
    assert cell.conf["name"] == "toy" and cell.mix["rate"] == 9.0
    assert [m["name"] for m in cell.per_layer] == ["toy_metric"]
    # No workloads key on an end-to-end metric: every cell reports it.
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert registry.load_reader("toy_metric", root)(None) == 42.0
    with pytest.raises(KeyError):
        registry.load_cell("no.such.cell", root)


def test_every_cell_reports_what_its_metrics_move():
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = registry.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)
        assert cell.conf["name"] == w["config"]
        for m in cell.per_layer:
            assert callable(registry.load_reader(m["name"]))


def test_run_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deepseek-llm-7b.session",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 3
    assert p.stdout == ""


@pytest.fixture
def fault(request, monkeypatch):
    """Break the timed path underneath the harness."""
    from repro.models import transformer
    from repro.serving import sampling

    kind = request.param
    if kind == "state_unchanged":
        real = transformer.decode_step

        def decode_step(params, cfg, token, caches, lengths, **kw):
            logits, _ = real(params, cfg, token, caches, lengths, **kw)
            return logits, caches

        monkeypatch.setattr(transformer, "decode_step", decode_step)
    elif kind == "half_batch":
        real = transformer.decode_step

        def decode_step(params, cfg, token, caches, lengths, **kw):
            logits, new = real(params, cfg, token, caches, lengths, **kw)
            rows = jnp.arange(logits.shape[0])[:, None]
            return jnp.where(rows % 2 == 1, 0.0, logits), new

        monkeypatch.setattr(transformer, "decode_step", decode_step)
    elif kind == "token_altered":
        real = sampling._sample_batch

        def sample(logits, *a):
            tok = real(logits, *a)
            return (tok + 1) % logits.shape[-1]

        monkeypatch.setattr(sampling, "_sample_batch", sample)
    return kind


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch",
                                   "token_altered"], indirect=True)
def test_correct_only_when_the_timed_path_is_sound(root, fault):
    out = _run(root, "tiny.session")
    gap = out.result["checks"]["max_logit_gap"]
    assert out.result["checks"]["tokens_compared"]["value"] >= 8
    if fault == "none":
        assert out.result["correct"], gap
        assert out.result["attempted"] > 0 and out.result["failed"] == 0
    else:
        assert not out.result["correct"], gap
    assert list(out.result)[-1] == "checks"


def test_the_float8_control_fails_the_limit(root):
    out = _run(root, "tiny.session", control=True)
    assert out.result["correct"]
    # The control's first choices go through the comparison that decides
    # ``correct``, at the same positions, and come out as not correct.
    assert out.control["tokens_compared"] == out.result["checks"]["tokens_compared"]
    assert not check.passed(out.control)
    assert not check.passed(check.decide(
        {k: v["value"] for k, v in out.control.items()}, tiny.LIMITS))
    gap = out.control["max_logit_gap"]["value"]
    assert gap > 3 * out.result["checks"]["max_logit_gap"]["value"]


def test_traced_run_reports_span_and_counter_metrics(root, monkeypatch):
    monkeypatch.setattr(run.work, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    out = _run(root, "tiny.chat", trace=True, seconds=3.0)
    m = out.result["metrics"]
    for name in ("queue_wait_p90_ms", "prefix_hit_share", "decode_tick_ms",
                 "flush_share"):
        assert name in m and m[name]["value"] >= 0, name
    assert 0 < m["prefix_hit_share"]["value"] < 1
    # The CPU has no TPU plane: device-trace metrics are left out, not 0.
    assert "device_idle_share" not in m and "paged_decode_roofline" not in m
    assert set(out.result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out.result["correct"]
