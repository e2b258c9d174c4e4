"""The serving engine's own spans and events on the CPU at a tiny size:
the span tree, the numbers the spans and events carry against what the
benchmark rebuilds from outside the program, the names of the engine's
device programs, and the spans on the profiler's host trace."""

import logging
import re
import time
import types

import jax
import pytest

from bench import context, driver, model, registry, run, trace_reduce, traffic
from bench.tests import tiny

SEED = 2**31 + 707

#: Each engine span and the span it opens inside.
PARENT = {
    "step": None, "schedule": "step", "flush": "step", "decode": "step",
    "prefill": "flush", "sample_first": "flush", "decode.launch": "decode",
    "decode.sync": "decode", "decode.replay": "decode",
}


class _Compiled(logging.Handler):
    """Names of the modules JAX compiles. Its debug line reads ``Compiling
    jit(<function>) ...``; the module, and a profiler trace, name it
    ``jit_<function>``."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.modules = set()

    def emit(self, record):
        m = re.match(r"Compiling jit\((\w+)\) ", record.getMessage())
        if m:
            self.modules.add("jit_" + m.group(1))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced window of the tiny chat cell (prefills, extends and decode
    scans), with the tracer on and ``run.Probe`` attached."""
    from repro.obs import Telemetry

    root = tiny.make_root(tmp_path_factory.mktemp("checkout"))
    cell = registry.load_cell("tiny.chat", root)
    cfg = model.model_config(cell.conf)
    params = model.make_params(cfg, SEED)
    tel = Telemetry.create()
    compiled = _Compiled()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    level = logger.level
    logger.addHandler(compiled)
    logger.setLevel(logging.DEBUG)
    try:
        engine, num_pages = run.build_engine(cfg, params, cell, tel)
        run.warm_up(engine, cfg, cell, SEED)
    finally:
        logger.removeHandler(compiled)
        logger.setLevel(level)
    seconds = 2.0
    reqs = traffic.open_requests(cell.mix, cfg.vocab, SEED, seconds)
    probe = run.Probe(engine.backend, num_pages)
    tel.reset()
    log_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(log_dir)):
        t0 = time.perf_counter()
        window = driver.run_window(
            driver.Engine(engine), seconds=seconds, open_reqs=reqs,
            annotate=jax.profiler.TraceAnnotation, on_step=probe.on_step)
    probe.detach()
    ctx = context.Ctx(
        window=window, t0=t0, max_batch=cell.mix["max_batch"],
        page_size=cell.conf["engine"]["page_size"], num_pages=num_pages,
        shape=model.work_shape(cell.conf), peak={}, spans=list(tel.tracer.spans),
        events=dict(tel.tracer.requests), prefix_before={}, prefix_after={},
        pool_used=probe.pool_used, admissions=probe.admissions,
        trace=trace_reduce.read(trace_reduce.find_xplane(log_dir)),
    )
    return types.SimpleNamespace(ctx=ctx, modules=compiled.modules)


def test_span_tree(served):
    spans = served.ctx.spans
    assert {s.name for s in spans} == set(PARENT)
    by_sid = {s.sid: s for s in spans}
    assert len(by_sid) == len(spans)
    children = {s.sid: [] for s in spans}
    for s in spans:
        if PARENT[s.name] is None:
            assert s.parent is None
            continue
        parent = by_sid[s.parent]
        assert parent.name == PARENT[s.name], s
        assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
        assert s.depth == parent.depth + 1
        children[parent.sid].append(s)
    for s in spans:
        kids = sorted(children[s.sid], key=lambda c: c.t0)
        # Siblings follow one another, so a span's self time is its duration
        # less its children's.
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0
        assert s.duration - sum(c.duration for c in kids) >= 0
    # One prefill span per launch, naming its rows.
    prefills = [s for s in spans if s.name == "prefill"]
    assert prefills and all(
        len(s.args["uids"]) == len(s.args["tail_tokens"])
        == len(s.args["prefix_tokens"]) == s.args["rows"] for s in prefills)
    assert any(s.args["prefix_pages"] > 0 for s in prefills)


def test_decode_kv_lens_match_the_rebuilt_ticks(served):
    decode = served.ctx.span_list("decode")
    assert all(s.args["ticks"] == len(s.args["kv_lens"]) for s in decode)
    from_spans = [sorted(t) for s in decode for t in s.args["kv_lens"]]
    rebuilt = [sorted(t) for t in served.ctx.ticks()]
    assert from_spans and from_spans == rebuilt


def test_admitted_args_match_the_probe(served):
    admitted = [
        (uid, args["prompt_tokens"], args["reused_pages"])
        for uid, events in served.ctx.events.items()
        for name, _, args in events if name == "admitted"
    ]
    assert admitted and sorted(admitted) == sorted(served.ctx.admissions)
    assert any(reused > 0 for _, _, reused in admitted)


def test_first_sampled_precedes_first_token(served):
    n = 0
    for uid, events in served.ctx.events.items():
        names = [name for name, _, _ in events]
        if "first_token" not in names:
            continue
        n += 1
        assert "first_sampled" in names[:names.index("first_token")], uid
    assert n > 0


def test_engine_programs_have_stable_names(served):
    assert {"jit_decode_scan", "jit_prefill", "jit_extend",
            "jit_scatter_tail"} <= served.modules
    assert not {"jit_run", "jit_f", "jit__lambda"} & served.modules


def test_engine_spans_reach_the_profiler_host_trace(served):
    names = {h.name for h in served.ctx.trace.host}
    assert {"step", "flush", "prefill", "decode", "decode.sync"} <= names
