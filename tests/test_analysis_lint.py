"""Rule coverage for the NUMA-contract linter (repro.analysis.lint).

Two halves per the PR-6 acceptance bar:
  * every registered rule demonstrably *fires* on a known-bad fixture
    snippet (linted via ``lint_source`` at a virtual path, so no bad file
    ever exists in the tree), and
  * the live tree is *clean*: ``python -m repro.analysis --strict``
    exits 0.
"""

import pytest

from repro.analysis import RULES, lint_source, run_rules
from repro.analysis.lint import main


def _fires(source, path, rule):
    vs = lint_source(source, path, rules=[rule])
    assert vs, f"rule {rule} did not fire on its bad fixture"
    assert all(v.rule == rule for v in vs)
    return vs


# --- each rule fires on its bad fixture --------------------------------------


def test_versioned_jax_rule_fires():
    bad = "from jax.experimental.pallas import tpu\np = tpu.TPUCompilerParams()\n"
    vs = _fires(bad, "src/repro/kernels/evil.py", "compat-only-versioned-jax")
    assert "TPUCompilerParams" in vs[0].message


def test_versioned_jax_rule_ignores_strings_and_compat():
    # The old text grep would have flagged the docstring; the AST rule
    # only sees real identifiers.
    doc = '"""mentions TPUCompilerParams in prose only"""\nx = 1\n'
    assert lint_source(doc, "src/repro/kernels/doc.py",
                       rules=["compat-only-versioned-jax"]) == []
    inside = "import jax\np = jax.AxisType\n"
    assert lint_source(inside, "src/repro/compat.py",
                       rules=["compat-only-versioned-jax"]) == []


def test_plan_dispatch_rule_fires():
    bad = "from repro.kernels.ops import resolve_mapping\n" \
          "mc = resolve_mapping((1, 8, 8, 128, 128, 64))\n"
    _fires(bad, "src/repro/serving/engine.py", "plan-dispatch-only")
    # the same source at a non-dispatch path is fine
    assert lint_source(bad, "src/repro/kernels/plan.py",
                       rules=["plan-dispatch-only"]) == []


def test_plan_dispatch_rule_catches_keywords():
    bad = "def f(attn):\n    return attn(x, q_offset=3)\n"
    _fires(bad, "src/repro/models/attention.py", "plan-dispatch-only")


def test_legacy_engine_rule_fires():
    bad = "from repro.serving import ServingEngine\n" \
          "e = ServingEngine(cfg, params)\n"
    _fires(bad, "examples/quickstart.py", "no-legacy-engine-construction")
    # construction inside serving/ (the shims' own home) is allowed
    assert lint_source(bad, "src/repro/serving/engine.py",
                       rules=["no-legacy-engine-construction"]) == []
    # naming the class without calling it (e.g. isinstance) is allowed
    ref = "from repro.serving import ServingEngine\n" \
          "ok = isinstance(x, ServingEngine)\n"
    assert lint_source(ref, "examples/quickstart.py",
                       rules=["no-legacy-engine-construction"]) == []


def test_decode_relevance_rule_fires_on_missing_predicate():
    bad = "def kernel(length, window):\n" \
          "    lo = length - window\n" \
          "    return lo\n"
    vs = _fires(bad, "src/repro/kernels/decode_attention.py",
                "decode-relevance-shared")
    kinds = "\n".join(v.message for v in vs)
    assert "chunk_relevant" in kinds
    assert "combine_split_states" in kinds
    assert "window-edge" in kinds


def test_decode_relevance_rule_ignores_other_files():
    bad = "lo = length - window\n"
    assert lint_source(bad, "src/repro/kernels/decode_common.py",
                       rules=["decode-relevance-shared"]) == []


def test_pallas_compat_rule_fires_outside_kernels():
    bad = "import jax.experimental.pallas as pl\n" \
          "fn = pl.pallas_call(k, out_shape=o)\n"
    vs = _fires(bad, "src/repro/serving/backends.py",
                "pallas-call-via-compat")
    assert "outside src/repro/kernels/" in vs[0].message


def test_pallas_compat_rule_fires_on_missing_compiler_params():
    bad = "import jax.experimental.pallas as pl\n" \
          "fn = pl.pallas_call(k, out_shape=o)\n"
    _fires(bad, "src/repro/kernels/newkernel.py", "pallas-call-via-compat")
    good = (
        "import jax.experimental.pallas as pl\n"
        "from repro import compat\n"
        "fn = pl.pallas_call(k, out_shape=o,\n"
        "    compiler_params=compat.tpu_compiler_params())\n"
    )
    assert lint_source(good, "src/repro/kernels/newkernel.py",
                       rules=["pallas-call-via-compat"]) == []


def test_host_sync_rule_fires():
    bad = (
        "import numpy as np\n"
        "class B:\n"
        "    def decode(self, tok):\n"
        "        x = np.asarray(tok)\n"
        "        n = self.lengths.item()\n"
        "        self.caches.block_until_ready()\n"
        "        return x, n\n"
    )
    vs = _fires(bad, "src/repro/serving/backends.py",
                "no-host-sync-in-decode-hot-loop")
    assert len(vs) == 3  # asarray + item + block_until_ready


def test_host_sync_rule_scoped_to_hot_loop():
    # _advance is the sanctioned sync point: same calls, no violation.
    ok = (
        "import numpy as np\n"
        "class E:\n"
        "    def _advance(self, tok, logits):\n"
        "        return np.asarray(logits).item()\n"
    )
    assert lint_source(ok, "src/repro/serving/engine.py",
                       rules=["no-host-sync-in-decode-hot-loop"]) == []
    # and jnp.asarray in the hot loop is fine (device-side, no sync)
    ok2 = (
        "import jax.numpy as jnp\n"
        "class B:\n"
        "    def decode(self, tok):\n"
        "        return jnp.asarray(tok)\n"
    )
    assert lint_source(ok2, "src/repro/serving/backends.py",
                       rules=["no-host-sync-in-decode-hot-loop"]) == []


def test_obs_hot_loop_allocs_rule_fires():
    bad = (
        "class E:\n"
        "    def step(self):\n"
        "        c = self.telemetry.metrics.counter('steps')\n"
        "        c.inc()\n"
        "    def _decode_tick(self):\n"
        "        self.registry.histogram('decode_s').observe(0.1)\n"
    )
    vs = _fires(bad, "src/repro/serving/engine.py", "obs-no-hot-loop-allocs")
    assert len(vs) == 2  # counter in step + histogram in _decode_tick
    assert "pre-bind at construction" in vs[0].message


def test_obs_hot_loop_allocs_rule_allows_prebound_use():
    # Registration in __init__ and .inc()/.observe() on the bound
    # instrument in the hot loop are exactly the sanctioned pattern.
    ok = (
        "class E:\n"
        "    def __init__(self, m):\n"
        "        self._m_steps = m.counter('steps')\n"
        "        self._h_step = m.histogram('step_s')\n"
        "    def step(self):\n"
        "        self._m_steps.inc()\n"
        "        self._h_step.observe(0.1)\n"
    )
    assert lint_source(ok, "src/repro/serving/engine.py",
                       rules=["obs-no-hot-loop-allocs"]) == []
    # the same registration outside serving/ is out of scope
    bad_path = (
        "class E:\n"
        "    def step(self):\n"
        "        self.m.counter('steps').inc()\n"
    )
    assert lint_source(bad_path, "src/repro/launch/loadgen.py",
                       rules=["obs-no-hot-loop-allocs"]) == []


def test_collectives_rule_fires():
    bad = (
        "import jax\n"
        "def kernel(x):\n"
        "    return jax.lax.psum(x, 'model')\n"
    )
    vs = _fires(bad, "src/repro/kernels/paged_decode_attention.py",
                "collectives-only-in-combine")
    assert "psum" in vs[0].message
    # the scheduler and the page pool must stay device-pure too
    _fires(bad, "src/repro/serving/scheduler.py",
           "collectives-only-in-combine")
    _fires(bad, "src/repro/cache/pool.py",
           "collectives-only-in-combine")


def test_collectives_rule_allows_sanctioned_modules():
    src = (
        "import jax\n"
        "def combine(parts):\n"
        "    return jax.lax.psum(parts, 'model')\n"
        "def gather(x):\n"
        "    return jax.lax.all_gather(x, 'model')\n"
    )
    assert lint_source(src, "src/repro/kernels/decode_common.py",
                       rules=["collectives-only-in-combine"]) == []
    assert lint_source(src, "src/repro/serving/sampling.py",
                       rules=["collectives-only-in-combine"]) == []
    # the head-parallel kernel dispatch re-gathers per-device outputs
    assert lint_source(src, "src/repro/kernels/ops.py",
                       rules=["collectives-only-in-combine"]) == []
    # outside the scoped dirs (e.g. optim's gradient allreduce) the rule
    # does not apply
    assert lint_source(src, "src/repro/optim/grad_compress.py",
                       rules=["collectives-only-in-combine"]) == []


def test_kv_scales_rule_fires_on_indexing_and_arithmetic():
    bad_index = (
        "def peek(cache, pid):\n"
        "    k_scales = cache['k_scales']\n"
        "    return k_scales[:, pid]\n"
    )
    vs = _fires(bad_index, "src/repro/serving/backends.py",
                "kv-scales-ride-page-table")
    assert "k_scales" in vs[0].message
    bad_math = (
        "def dequant(codes, v_scales):\n"
        "    return codes * v_scales\n"
    )
    _fires(bad_math, "src/repro/serving/engine.py",
           "kv-scales-ride-page-table")
    _fires(bad_math, "examples/serve_longctx.py",
           "kv-scales-ride-page-table")


def test_kv_scales_rule_allows_opaque_passthrough_and_kernel_math():
    # Dict-key plumbing (how serving hands scales to the kernel call) and
    # keyword threading never touch the array's values — allowed anywhere.
    ok = (
        "def pack(cache):\n"
        "    return {'k_scales': cache['k_scales'],\n"
        "            'v_scales': cache.get('v_scales')}\n"
        "def call(op, cache):\n"
        "    return op(k_scales=cache['k_scales'])\n"
    )
    assert lint_source(ok, "src/repro/serving/backends.py",
                       rules=["kv-scales-ride-page-table"]) == []
    # Inside the kernel / quantization layers the math is the point.
    math = (
        "def dequant(codes, k_scales, pid):\n"
        "    return codes * k_scales[:, pid]\n"
    )
    assert lint_source(math, "src/repro/kernels/paged_decode_attention.py",
                       rules=["kv-scales-ride-page-table"]) == []
    assert lint_source(math, "src/repro/cache/quant.py",
                       rules=["kv-scales-ride-page-table"]) == []


# --- registry / CLI / live tree ----------------------------------------------


def test_every_registered_rule_has_a_bad_fixture_test():
    """Adding a rule without a firing fixture above must fail loudly."""
    covered = {
        "compat-only-versioned-jax", "plan-dispatch-only",
        "no-legacy-engine-construction", "decode-relevance-shared",
        "pallas-call-via-compat", "no-host-sync-in-decode-hot-loop",
        "obs-no-hot-loop-allocs", "collectives-only-in-combine",
        "kv-scales-ride-page-table",
    }
    assert set(RULES) == covered


def test_live_tree_is_clean():
    assert run_rules() == []


def test_cli_strict_exits_zero(capsys):
    assert main(["--strict"]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_rejects_unknown_rule():
    with pytest.raises(KeyError):
        run_rules(rules=["no-such-rule"])
