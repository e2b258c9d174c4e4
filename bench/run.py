"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: check the device, make the weights from the seed, build the
engine, warm up exactly the programs the cell's traffic can reach, set up
its state (session histories), serve for ``--seconds``, then check what was
served against the plain reference. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` profiles the same window and reports its
per-layer metrics. The last line of stdout is the result; the numbers
compared for ``correct`` are the last lines of stderr and the last key of
the result. With no TPU, or fewer chips than the cell needs, it exits 3 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import check, context, driver, model, registry, trace_reduce, traffic, work  # noqa: E402

_DEVICE = ""


def log(msg: str) -> None:
    print(f"[bench {_DEVICE}] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(jax, chips: int) -> dict:
    """The chips JAX sees; exits 3 (no result) without a TPU or enough."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[bench] needs {chips} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform!r} device(s)", file=sys.stderr)
        sys.exit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCount:
    """Backend compiles (or persistent-cache loads) and their seconds."""

    def __init__(self, jax):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def build_engine(cfg, params, cell, telemetry):
    from repro.serving import LLMEngine

    eng, mix = cell.conf["engine"], cell.mix
    ps = eng["page_size"]
    max_pages = mix["max_context"] // ps
    num_pages = mix["max_batch"] * max_pages + 1 + mix["prefix_room_pages"]
    engine = LLMEngine(
        cfg, params, kv_layout=eng["kv_layout"], max_batch=mix["max_batch"],
        prompt_buckets=tuple(mix["prompt_buckets"]), num_pages=num_pages,
        page_size=ps, max_pages_per_seq=max_pages,
        steps_per_sync=eng["steps_per_sync"],
        batch_prefills=eng["batch_prefills"], telemetry=telemetry,
    )
    return engine, num_pages


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def warm_up(engine, cfg, cell, seed: int) -> int:
    """Compile and run once every program the cell's traffic can reach:
    the first-token sampler for every number of rows one flush can admit,
    and one prefill per (tail bucket, prefix page bucket) pair the mix can
    produce, each through the engine itself. Returns the requests sent."""
    import jax

    from repro.serving import Request, SamplingParams, sampling

    mix, ps = cell.mix, cell.conf["engine"]["page_size"]
    for k in range(1, mix["max_batch"] + 1):
        z = np.zeros((k,), np.int32)
        jax.block_until_ready(sampling.sample_tokens(
            np.zeros((k, cfg.vocab), np.float32), np.zeros((k,), np.float32),
            z, np.ones((k,), np.float32), z, z))
    buckets = sorted(mix["prompt_buckets"])
    rng = np.random.default_rng([int(seed) % (1 << 64), 99])
    bases, extends = {}, []
    for (pmin, pmax), (tmin, tmax) in traffic.reachable_prefills(mix, ps):
        tails = []
        for i, b in enumerate(buckets):
            lo = buckets[i - 1] + 1 if i else 1
            if lo <= tmax and b >= tmin:
                tails.append(max(tmin, lo))
        pages = []
        if pmax > 0:
            p_lo, p_hi = -(-pmin // ps), pmax // ps
            m = p_lo
            while m <= p_hi:
                pages.append(m)
                m = _pow2(m) + 1
        for t in tails:
            for m in pages or [0]:
                if m and m not in bases:
                    bases[m] = rng.integers(1, cfg.vocab, m * ps).astype(np.int32)
                head = bases[m] if m else np.zeros((0,), np.int32)
                extends.append(np.concatenate(
                    [head, rng.integers(1, cfg.vocab, t).astype(np.int32)]))
    uid = 1 << 40

    def reqs(prompts):
        nonlocal uid
        out = []
        for p in prompts:
            uid += 1
            out.append(Request(uid, p, SamplingParams(max_tokens=1)))
        return out

    engine.generate(reqs(bases.values()))
    engine.generate(reqs(extends))
    return len(bases) + len(extends)


def prefill_histories(engine, sessions) -> None:
    """Session state the window starts from: every history in the cache."""
    from repro.serving import Request, SamplingParams

    engine.generate([
        Request((1 << 41) + i, s.history, SamplingParams(max_tokens=1))
        for i, s in enumerate(sessions)
    ])


class Probe:
    """Counters a traced run reads around the engine's calls: pool pages
    held after each step, and each admission's prompt length and reused
    prefix pages (from the record ``try_admit`` returns)."""

    def __init__(self, backend, num_pages: int):
        self.backend, self.num_pages = backend, num_pages
        self.pool_used, self.admissions = [], []
        self._admit = backend.try_admit
        backend.try_admit = self._try_admit

    def _try_admit(self, req, *a, **kw):
        rec = self._admit(req, *a, **kw)
        if isinstance(rec, dict):
            self.admissions.append((req.uid, int(rec["n"]), len(rec["matched"])))
        return rec

    def on_step(self) -> None:
        free = self.backend.prefix_stats()["free_pages"]
        self.pool_used.append(1.0 - free / (self.num_pages - 1))

    def detach(self) -> None:
        self.backend.try_admit = self._admit
        self.backend = self._admit = None


@dataclasses.dataclass
class Outcome:
    result: dict            # the result line
    window: driver.Window
    readings: dict          # the numbers compared
    control: Optional[dict] = None  # the control's checks, with ``control``


def run_cell(cell, *, seed: int, seconds: float, trace: bool, device: dict,
             root: pathlib.Path = ROOT, t_start: float, compiles,
             control: bool = False) -> Outcome:
    """Set up, serve one window, read its metrics, check what it served."""
    import jax

    from repro.obs import Telemetry

    device = dict(device)
    cfg = cell.family.model_config(cell.conf)
    params = model.make_params(cfg, seed)
    jax.block_until_ready(params)
    telemetry = Telemetry.create() if trace else None
    engine, num_pages = build_engine(cfg, params, cell, telemetry)
    n_warm = warm_up(engine, cfg, cell, seed)
    mix = cell.mix
    vocab = cfg.vocab
    open_reqs, sess, sess_due = [], [], []
    if mix["loop"] == "open":
        open_reqs = traffic.open_requests(mix, vocab, seed, seconds)
    else:
        sess = traffic.sessions(mix, vocab, seed)
        sess_due = traffic.first_turn_due(mix, seed)
        prefill_histories(engine, sess)
    gc.collect()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.4f} warm-up requests={n_warm} "
        f"compiles={compiles.n} compile_s={compiles.seconds:.3f} "
        f"cache_hits={compiles.cache_hits}")

    backend = engine.backend
    probe = Probe(backend, num_pages) if trace else None
    annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    if trace:
        annotate = jax.profiler.TraceAnnotation
        trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        telemetry.reset()
    prefix_before = backend.prefix_stats()
    n_before = compiles.n
    if trace:
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    t0 = time.perf_counter()
    window = driver.run_window(
        driver.Engine(engine), seconds=seconds, open_reqs=open_reqs,
        sessions=sess, session_due=sess_due, annotate=annotate,
        on_step=probe.on_step if probe else None,
    )
    if trace:
        jax.profiler.stop_trace()
        probe.detach()
    in_window = compiles.n - n_before
    prefix_after = backend.prefix_stats()
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    late = sorted(window.lateness) or [0.0]
    log(f"generator lateness s: p50={driver.percentile(late, 50):.6f} "
        f"p99={driver.percentile(late, 99):.6f} max={late[-1]:.6f}; "
        f"compiles in window={in_window}; steps={len(window.steps)}; "
        f"memory_peak_bytes={device['memory_peak_bytes']}")

    due = [r for r in window.records if r.due <= seconds]
    result = {
        "correct": False,
        "attempted": len(due),
        "failed": sum(1 for r in due if r.failed),
        "metrics": {},
        "device": device,
    }
    if trace:
        trace = trace_reduce.read(trace_reduce.find_xplane(trace_dir.name))
        ctx = context.Ctx(
            window=window, t0=t0, max_batch=mix["max_batch"],
            page_size=cell.conf["engine"]["page_size"], num_pages=num_pages,
            shape=cell.family.work_stack(cell.conf), peak=work.peaks(device["kind"]),
            spans=list(telemetry.tracer.spans),
            events=dict(telemetry.tracer.requests),
            prefix_before=prefix_before, prefix_after=prefix_after,
            pool_used=probe.pool_used, admissions=probe.admissions,
            trace=trace,
        )
        for m in cell.per_layer:
            v = registry.load_reader(m["name"], root)(ctx)
            if v is not None and math.isfinite(v):
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_s(trace)
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                               "idle_gaps": trace_reduce.idle_gaps(trace)}
        trace_dir.cleanup()
    else:
        e2e = driver.end_to_end(window)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}

    # Correctness, once the program's state is freed.
    picked = check.sample(window.records, seed)
    engine = backend = None
    gc.collect()
    read, ctrl = (check.readings(params, cell, picked, control)
                  if picked else ({}, None))
    checks = check.decide(read, cell.limits)
    result["correct"] = check.passed(checks)
    result["checks"] = checks
    return Outcome(result=result, window=window, readings=read,
                   control=check.decide(ctrl, cell.limits) if ctrl else None)


def main(argv=None) -> dict:
    global _DEVICE
    args = parse(argv)
    cell = registry.load_cell(args.workload, ROOT)
    import jax

    device = device_info(jax, cell.chips)
    _DEVICE = f"{device['platform']} {device['kind']} x{device['count']}"
    from repro import compat

    cache_dir = compat.enable_compilation_cache()
    log(f"workload={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} compile_cache={cache_dir}")
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), device=device,
                   t_start=T_START, compiles=CompileCount(jax))
    for name, c in out.result["checks"].items():
        log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(out.result), flush=True)
    return out.result


if __name__ == "__main__":
    main()
