"""Trace-driven SLO load harness over the ``LLMEngine`` facade (PR 7).

Open-loop load generation with the full telemetry stack attached:

  * **Arrivals** are Poisson — exponential inter-arrival gaps at
    ``--rate`` requests/s, cumulative-summed into a wall-clock schedule.
    The driver releases each request when its arrival time passes, steps
    the engine continuously while it has work, and sleeps to the next
    arrival when idle — so queueing delay is *measured*, not simulated.
  * **Workload mix**: prompt lengths and output budgets are drawn from
    weighted mixes, and a configurable fraction of requests shares a
    system-prompt prefix (page-aligned, so the paged backend's prefix
    cache gets real hits).
  * **Warmup**: a pilot batch runs to completion first (compiling every
    prefill bucket the mix can hit), ``jax.block_until_ready`` drains the
    device, and ``engine.reset_metrics()`` zeroes telemetry — measured
    numbers never include compilation.
  * **SLO metrics**: TTFT / ITL p50/p90/p99 from the tracer's lifecycle
    events (exact per-request timestamps, not averages), measured
    decode tok/s vs the analytic model's prediction, preemption and
    prefix-hit counters.
  * **Artifacts**: ``artifacts/benchmarks/loadgen_<layout>.json`` (the
    ``repro.obs`` envelope, with the full metrics snapshot riding along)
    and ``loadgen_<layout>_trace.json`` — a Chrome ``trace_event`` file;
    load it at https://ui.perfetto.dev. The model-vs-measured drift
    table (ROADMAP 5(b)) prints and lands in the JSON payload.

Run:
  PYTHONPATH=src python -m repro.launch.loadgen --smoke
      # CI: both KV layouts on the smoke model (Pallas in interpret
      # mode on CPU), asserts artifacts + latency coverage
  PYTHONPATH=src python -m repro.launch.loadgen --arch llama3-8b \
      --kv-layout paged --requests 64 --rate 32
  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python -m repro.launch.loadgen --smoke --mesh-sweep 1,2,4
      # sharded scaling sweep: head-sharded paged pool, global batch
      # scaled as devices * per-device rows, writes loadgen_sharded.json
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import compat
from repro.configs import registry
from repro.models import transformer
from repro.obs import Telemetry
from repro.obs.metrics import write_json_artifact
from repro.serving import LLMEngine, Request, SamplingParams

#: (value, weight) mixes the smoke/default workload draws from.
PROMPT_MIX: Tuple[Tuple[int, float], ...] = ((8, 0.5), (24, 0.3), (44, 0.2))
OUTPUT_MIX: Tuple[Tuple[int, float], ...] = ((4, 0.6), (8, 0.3), (12, 0.1))


def _draw(rng, mix) -> int:
    vals, weights = zip(*mix)
    w = np.asarray(weights, np.float64)
    return int(rng.choice(np.asarray(vals), p=w / w.sum()))


def build_workload(
    cfg,
    rng,
    n_requests: int,
    *,
    rate: float,
    prompt_mix=PROMPT_MIX,
    output_mix=OUTPUT_MIX,
    shared_prefix_len: int = 16,
    shared_fraction: float = 0.5,
    temperature: float = 0.0,
) -> List[Tuple[float, Request]]:
    """Poisson-arrival request trace: ``[(arrival_s, Request), ...]``
    sorted by arrival. ``shared_fraction`` of requests start with one
    common system prefix of ``shared_prefix_len`` tokens (page-align it
    to the backend's page size so prefix sharing can actually hit)."""
    gaps = rng.exponential(1.0 / rate, size=n_requests)
    arrivals = np.cumsum(gaps)
    system = rng.integers(1, cfg.vocab, size=(shared_prefix_len,))
    out: List[Tuple[float, Request]] = []
    for i in range(n_requests):
        tail_len = _draw(rng, prompt_mix)
        tail = rng.integers(1, cfg.vocab, size=(tail_len,))
        if shared_prefix_len and rng.random() < shared_fraction:
            prompt = np.concatenate([system, tail])
        else:
            prompt = tail
        out.append((float(arrivals[i]), Request(
            uid=i, prompt=prompt,
            sampling=SamplingParams(
                temperature=temperature,
                max_tokens=_draw(rng, output_mix),
            ),
        )))
    return out


def safe_div(num: float, den: float) -> float:
    return num / den if den else 0.0


def percentiles(values, qs=(50, 90, 99)) -> Dict[str, Optional[float]]:
    if not values:
        return {f"p{q}": None for q in qs}
    arr = np.asarray(values, np.float64)
    return {f"p{q}": float(np.percentile(arr, q)) for q in qs}


def _per_device_accounting(engine, cfg, done, peak_pages: int):
    """Per-device bandwidth + page-occupancy accounting for mesh runs.

    The pool is head-sharded: every page holds one KV-head slice per
    device, so page occupancy is identical on every device and the
    per-device footprint is ``pages * page_slice_bytes``. Decode reads
    the whole resident context once per step on every device (its head
    slice of it), so the implied per-device HBM demand is the per-token
    slice read times the measured aggregate token rate."""
    num_devices = engine.backend.num_devices
    if num_devices <= 1:
        return None
    from repro.distributed import sharding as sharding_lib

    b = engine.backend
    itemsize = jax.tree_util.tree_leaves(b.caches)[0].dtype.itemsize
    heads_per_dev = -(-cfg.n_kv_heads // num_devices)
    mean_ctx = float(np.mean(
        [o.prompt_len + len(o.tokens) for o in done])) if done else 0.0
    # One decode step reads each active row's resident KV once per
    # device (the head slice); the step yields one token per row, so
    # per-token-per-device bytes is independent of batch.
    kv_read = (2 * cfg.n_layers * heads_per_dev * mean_ctx
               * cfg.head_dim * itemsize)
    out = {
        "num_devices": num_devices,
        "kv_head_shards": [
            list(s) for s in
            sharding_lib.kv_head_shards(cfg.n_kv_heads, num_devices)
        ],
        "kv_read_bytes_per_token_per_device": kv_read,
        "implied_hbm_bw_per_device":
            kv_read * engine.stats().measured_tok_s,
    }
    pool = getattr(b, "pool", None)
    if pool is not None:
        slice_bytes = b._page_slice_bytes(
            cfg, b.page_size, num_devices, b.kv_dtype)
        out.update({
            "page_slice_bytes": slice_bytes,
            "pool_pages": pool.num_pages,
            "peak_pages_used": peak_pages,
            "peak_kv_bytes_per_device": peak_pages * slice_bytes,
            "page_budgets": b.device_page_budgets(),
        })
    return out


def _warmup(engine: LLMEngine, cfg, rng, workload) -> None:
    """Compile every prefill bucket the mix can hit (shared-prefix and
    bare variants), drain the device, zero telemetry."""
    pilots = []
    seen = set()
    for i, (_, req) in enumerate(workload):
        key = len(req.prompt)
        if key in seen:
            continue
        seen.add(key)
        pilots.append(Request(
            uid=10_000_000 + i, prompt=np.array(req.prompt),
            sampling=SamplingParams(max_tokens=2),
        ))
    engine.generate(pilots)
    jax.block_until_ready(engine.backend.caches)
    # Warmup requests stay in the completion history (uids >= 10_000_000)
    # but every measured counter/span/drift sample restarts here.
    engine.reset_metrics()


def drive(engine: LLMEngine, workload, *, idle_sleep_cap: float = 0.01,
          on_step=None):
    """Open-loop drive: release requests at their arrival times, step
    while the engine has work, sleep to the next arrival when idle.
    Returns the finished ``RequestOutput`` list. ``on_step(engine)`` is
    called after every step (occupancy sampling for the mesh sweep)."""
    pending = sorted(workload, key=lambda a: a[0])
    done = []
    i = 0
    t0 = time.perf_counter()
    while i < len(pending) or engine.backend.active.any() \
            or engine.scheduler.has_work():
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i][0] <= now:
            engine.add_request(pending[i][1])
            i += 1
        if not engine.backend.active.any() and not engine.scheduler.has_work():
            # Idle with future arrivals only: sleep toward the next one.
            time.sleep(min(max(pending[i][0] - now, 0.0), idle_sleep_cap))
            continue
        done.extend(o for o in engine.step() if o.finished)
        if on_step is not None:
            on_step(engine)
    return done


def run_one(args, kv_layout: str, *, cfg=None) -> Dict:
    """One full load run on one KV layout; returns the summary payload
    (also written to ``artifacts/benchmarks/loadgen_<kv_layout>.json``).
    ``cfg`` overrides the registry lookup (the mesh sweep pins one
    mesh-divisible config so runs are comparable across device counts)."""
    if cfg is None:
        get_cfg = (registry.get_smoke_config if args.smoke
                   else registry.get_config)
        cfg = get_cfg(args.arch)
    # getattr throughout: programmatic callers hand-build the namespace
    # and may predate newer flags (tests/test_loadgen.py does).
    mesh_n = int(getattr(args, "mesh", 0) or 0)
    steps = getattr(args, "steps_per_sync", 1)
    if steps != "auto":
        steps = int(steps)
    kv_dtype = getattr(args, "kv_dtype", "fp32") or "fp32"
    host_pool = int(getattr(args, "host_pool_bytes", 0) or 0)
    params = transformer.init_model(jax.random.PRNGKey(args.seed), cfg)
    telemetry = Telemetry.create()
    engine = LLMEngine(
        cfg, params,
        kv_layout=kv_layout,
        max_batch=args.max_batch,
        cache_len=args.cache_len,
        num_pages=args.num_pages,
        page_size=args.page_size,
        prompt_buckets=(16, 32, 64),
        telemetry=telemetry,
        mesh=mesh_n if mesh_n > 1 else None,
        steps_per_sync=steps,
        kv_dtype=kv_dtype,
        host_pool_bytes=host_pool or None,
    )
    rng = np.random.default_rng(args.seed)
    workload = build_workload(
        cfg, rng, args.requests, rate=args.rate,
        shared_prefix_len=args.shared_prefix,
        shared_fraction=args.shared_fraction,
        temperature=args.temperature,
    )
    _warmup(engine, cfg, rng, workload)
    traces_warm = engine.backend.stats.get("decode_traces", 0)

    # Peak page occupancy, sampled after every step: with the
    # head-sharded pool each page spans all devices (one head-slice per
    # device), so pool occupancy IS the per-device occupancy.
    peak = {"pages": 0}

    def _sample(eng):
        pool = getattr(eng.backend, "pool", None)
        if pool is not None:
            peak["pages"] = max(peak["pages"], int(pool.used_pages))

    t0 = time.perf_counter()
    done = drive(engine, workload, on_step=_sample)
    wall = time.perf_counter() - t0
    retraces = engine.backend.stats.get("decode_traces", 0) - traces_warm

    lat = telemetry.tracer.request_latencies()
    measured = {uid: d for uid, d in lat.items() if uid < 10_000_000}
    ttft = [d["ttft"] for d in measured.values() if d["ttft"] is not None]
    queue = [d["queue"] for d in measured.values() if d["queue"] is not None]
    itl = [x for d in measured.values() for x in d["itl"]]
    stats = engine.stats()
    prefix = engine.backend.prefix_stats()
    drift = telemetry.drift.report(engine.drift_model_fn())

    from repro.core import perf_model

    # Per-token host overhead: the per-step residual (step wall minus its
    # schedule / flush / decode phases) over the tokens produced — output
    # sync, bookkeeping, span plumbing. This is the once-per-sync tax the
    # fused N-step scan amortizes; flush is excluded because prefill cost
    # (and any in-run compilation) is per-request, not per-token.
    snap = telemetry.metrics.snapshot()
    host_overhead = safe_div(
        stats.elapsed_s - stats.decode_elapsed_s
        - snap["serving_flush_seconds"]["sum"]
        - snap["serving_schedule_seconds"]["sum"],
        stats.tokens_generated,
    )
    payload = {
        "arch": args.arch,
        "smoke": bool(args.smoke),
        "kv_layout": engine.kv_layout,
        "requests": args.requests,
        "finished": len(done),
        "rate_req_s": args.rate,
        "wall_s": wall,
        "steps_per_sync": engine.steps_per_sync,
        "tokens_generated": stats.tokens_generated,
        "measured_tok_s": stats.measured_tok_s,
        "modeled_tok_s": stats.modeled_tok_s,
        "decode_elapsed_s": stats.decode_elapsed_s,
        "host_overhead_per_token_s": host_overhead,
        "modeled_host_overhead_s": perf_model.amortized_host_overhead(
            engine.steps_per_sync
        ),
        "decode_retraces_after_warmup": retraces,
        "ttft_s": percentiles(ttft),
        "itl_s": percentiles(itl),
        "queue_s": percentiles(queue),
        "preemptions": stats.preemptions,
        "resumed_tokens": stats.resumed_tokens,
        "prefix": prefix,
        "occupancy_cap": stats.occupancy_cap,
        "drift": drift.to_dict(),
        "drift_worst_ratio": drift.worst_ratio(),
        "mesh_devices": engine.backend.num_devices,
        "per_device": _per_device_accounting(engine, cfg, done, peak["pages"]),
    }
    out_dir = args.out_dir or None
    # N > 1 and mesh runs get their own artifact names so sweeps (the
    # smoke host-overhead comparison, the sharded device-count sweep)
    # never clobber the N=1 single-device baseline.
    n = engine.steps_per_sync
    stem = f"loadgen_{engine.kv_layout}" + (f"_n{n}" if n > 1 else "")
    if engine.backend.num_devices > 1:
        stem += f"_d{engine.backend.num_devices}"
    if host_pool:
        # Tiered runs get their own artifact: the demote/promote counters
        # in payload["prefix"] are the demonstration CI reads.
        stem = "loadgen_tiered"
    json_path = write_json_artifact(
        stem, payload,
        metrics=telemetry.metrics,
        dirpath=out_dir, kind="loadgen",
    )
    trace_dir = out_dir or os.path.dirname(json_path)
    trace_path = telemetry.tracer.write_chrome_trace(
        os.path.join(trace_dir, f"{stem}_trace.json")
    )
    payload["_artifacts"] = {"json": json_path, "trace": trace_path}

    def ms(d):
        return " / ".join(
            "n/a" if d[f"p{q}"] is None else f"{d[f'p{q}'] * 1e3:.1f}ms"
            for q in (50, 90, 99)
        )

    print(f"[loadgen:{engine.kv_layout}] {len(done)}/{args.requests} "
          f"finished in {wall:.2f}s at rate {args.rate}/s "
          f"(steps_per_sync={engine.steps_per_sync})")
    print(f"  host overhead {host_overhead * 1e6:.1f}us/token "
          f"(modeled {payload['modeled_host_overhead_s'] * 1e6:.1f}us), "
          f"{retraces} decode retraces after warmup")
    print(f"  TTFT p50/p90/p99: {ms(payload['ttft_s'])}")
    print(f"  ITL  p50/p90/p99: {ms(payload['itl_s'])}")
    print(f"  measured {stats.measured_tok_s:.1f} tok/s (decode wall "
          f"{stats.decode_elapsed_s:.2f}s), modeled "
          f"{stats.modeled_tok_s:.0f} tok/s")
    hit = prefix.get("prefix_hit_rate")
    print(f"  preemptions {stats.preemptions} "
          f"({stats.resumed_tokens} tokens resumed), prefix hit "
          f"{'n/a' if hit is None else f'{hit:.2f}'}")
    print("  " + drift.render().replace("\n", "\n  "))
    print(f"  wrote {json_path}")
    print(f"  wrote {trace_path} (open in https://ui.perfetto.dev)")
    engine.close()
    return payload


def _smoke_check(payload: Dict) -> None:
    """CI acceptance for one layout's run."""
    import json

    assert payload["finished"] == payload["requests"], payload
    assert payload["ttft_s"]["p50"] is not None, "no TTFT measured"
    assert payload["itl_s"]["p99"] is not None, "no ITL measured"
    assert payload["measured_tok_s"] > 0, "no measured throughput"
    assert payload["drift"]["rows"], "no drift cells recorded"
    with open(payload["_artifacts"]["trace"]) as f:
        trace = json.load(f)
    assert trace["traceEvents"], "empty Chrome trace"
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"X", "M", "i", "b", "e"} <= phases, phases
    with open(payload["_artifacts"]["json"]) as f:
        env = json.load(f)
    assert env["schema"] == "repro.obs/v1", env["schema"]
    assert env["metrics"]["serving_steps_total"]["value"] > 0


def run_sharded_sweep(args) -> Dict:
    """Device-count scaling sweep on the paged backend: one load run per
    mesh width with MaxText-style global-batch scaling
    (``max_batch = device_count * per_device_batch``, requests scaled to
    match), modeled + measured aggregate tok/s, per-device page and
    bandwidth accounting. Writes ``loadgen_sharded.json``."""
    counts = sorted({int(x) for x in args.mesh_sweep.split(",") if x})
    if not counts:
        raise ValueError("--mesh-sweep needs a comma-separated list of "
                         "device counts, e.g. 1,2,4")
    avail = len(jax.devices())
    if max(counts) > avail:
        raise RuntimeError(
            f"--mesh-sweep asks for {max(counts)} devices but the host has "
            f"{avail}: {sorted(c for c in counts if c > avail)} cannot run"
        )
    runnable = counts

    get_cfg = (registry.get_smoke_config if args.smoke
               else registry.get_config)
    cfg = get_cfg(args.arch)
    if args.smoke:
        # Pin ONE mesh-divisible head layout for the whole sweep so the
        # numbers are comparable across device counts (the smoke config's
        # Hkv=2 doesn't divide over 4 devices).
        cfg = dataclasses.replace(cfg, n_heads=8, n_kv_heads=4,
                                  head_dim=16, d_model=128, d_ff=256)
    bad = [d for d in runnable if cfg.n_kv_heads % d]
    if bad:
        raise ValueError(f"n_kv_heads={cfg.n_kv_heads} not divisible by "
                         f"device counts {bad}")

    per_dev_batch = int(getattr(args, "per_device_batch", 0)
                        or args.max_batch)
    runs: Dict[str, Dict] = {}
    for d in runnable:
        ns = argparse.Namespace(**vars(args))
        ns.mesh = d
        ns.max_batch = per_dev_batch * d
        ns.requests = args.requests * d
        print(f"[loadgen] sharded sweep: {d} device(s), "
              f"max_batch={ns.max_batch}, requests={ns.requests}")
        p = run_one(ns, "paged", cfg=cfg)
        if args.smoke:
            assert p["finished"] == p["requests"], p
            assert p["measured_tok_s"] > 0, p
        runs[str(d)] = {k: p[k] for k in (
            "requests", "finished", "wall_s", "steps_per_sync",
            "tokens_generated", "measured_tok_s", "modeled_tok_s",
            "decode_elapsed_s", "decode_retraces_after_warmup",
            "mesh_devices", "per_device",
        )}
        runs[str(d)]["max_batch"] = ns.max_batch

    base = runs[str(runnable[0])]
    payload = {
        "arch": args.arch,
        "smoke": bool(args.smoke),
        "kv_layout": "paged",
        "per_device_batch": per_dev_batch,
        "device_counts": runnable,
        "runs": runs,
        # Aggregate throughput relative to the smallest mesh — the
        # artifact the acceptance criterion reads (modeled AND measured
        # tok/s scaling vs device count).
        "scaling": {
            "baseline_devices": runnable[0],
            "measured_tok_s": {
                str(d): safe_div(runs[str(d)]["measured_tok_s"],
                                 base["measured_tok_s"])
                for d in runnable
            },
            "modeled_tok_s": {
                str(d): safe_div(runs[str(d)]["modeled_tok_s"],
                                 base["modeled_tok_s"])
                for d in runnable
            },
        },
    }
    path = write_json_artifact("loadgen_sharded", payload,
                               dirpath=args.out_dir or None,
                               kind="loadgen")
    print("[loadgen] sharded scaling (vs "
          f"{runnable[0]} device(s)):")
    for d in runnable:
        r = runs[str(d)]
        print(f"  {d}dev: measured {r['measured_tok_s']:.1f} tok/s "
              f"(x{payload['scaling']['measured_tok_s'][str(d)]:.2f}), "
              f"modeled {r['modeled_tok_s']:.0f} tok/s "
              f"(x{payload['scaling']['modeled_tok_s'][str(d)]:.2f})")
    print(f"[loadgen] wrote {path}")
    payload["_artifacts"] = {"json": path}
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="CI: smoke model, both KV layouts, assert "
                         "artifacts + latency coverage")
    ap.add_argument("--kv-layout", choices=("auto", "dense", "paged"),
                    default="auto")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--num-pages", type=int, default=96)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--shared-prefix", type=int, default=16,
                    help="system-prompt tokens (page-aligned) shared by "
                         "--shared-fraction of requests")
    ap.add_argument("--shared-fraction", type=float, default=0.5)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--steps-per-sync", default="1",
                    help="fused decode scan length N: the host syncs "
                         "(flush/schedule/telemetry) once per N tokens; "
                         "'auto' lets the scheduler pick from the live "
                         "batch's modeled tick time")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard one load run over N devices (1-D 'model' "
                         "mesh, head-sharded KV; 0 = single-device)")
    ap.add_argument("--mesh-sweep", default="",
                    help="comma-separated device counts (e.g. 1,2,4): "
                         "paged scaling sweep with per-device batch "
                         "scaling, writes loadgen_sharded.json")
    ap.add_argument("--per-device-batch", type=int, default=0,
                    help="mesh sweep: decode rows per device "
                         "(max_batch = devices * this; default "
                         "--max-batch)")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8", "fp8"),
                    default="fp32",
                    help="paged pool storage dtype (quantized codes + "
                         "per-page-per-head scales for int8/fp8)")
    ap.add_argument("--host-pool-bytes", type=int, default=0,
                    help="host-DRAM KV tier budget (0 = off); a tiered "
                         "run writes loadgen_tiered.json")
    ap.add_argument("--smoke-tiered", action="store_true",
                    help="CI: one paged run with a device pool too small "
                         "for the workload plus a host tier; asserts "
                         "demotions > 0 with zero preemptions and a "
                         "leak-free close")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=None,
                    help="artifact directory (default "
                         "artifacts/benchmarks)")
    args = ap.parse_args(argv)
    compat.enable_compilation_cache()

    if args.mesh_sweep:
        run_sharded_sweep(args)
        return

    if args.smoke_tiered:
        # Tiered acceptance: the device pool is (deliberately) too small
        # for the workload's working set, the host tier takes the spill.
        # The run must finish everything, demote real pages, and reclaim
        # capacity through demotion INSTEAD of preemption — then prove
        # the pool drained leak-free (run_one's engine.close()).
        if not args.host_pool_bytes:
            args.host_pool_bytes = 1 << 20
        payload = run_one(args, "paged")
        _smoke_check(payload)
        pf = payload["prefix"]
        assert pf["demoted_pages"] > 0, pf
        assert payload["preemptions"] == 0, (
            "capacity pressure should resolve by demotion, not preemption",
            payload["preemptions"])
        print(f"[loadgen] tiered smoke OK: {int(pf['demoted_pages'])} "
              f"demoted / {int(pf['promoted_pages'])} promoted, "
              f"0 preemptions, leak-free close")
        return

    if args.smoke:
        # Both layouts x N in {1, 8}: the fused-decode acceptance sweep.
        # Per (layout, N) run the standard smoke checks apply; across N
        # the N=8 run must hold the tentpole's guarantees — zero decode
        # retraces after warmup and strictly lower per-token host
        # overhead than the N=1 baseline.
        sweep: Dict[str, Dict[int, Dict]] = {}
        for layout in ("dense", "paged"):
            sweep[layout] = {}
            for n in (1, 8):
                args.steps_per_sync = n
                payload = run_one(args, layout)
                _smoke_check(payload)
                if n > 1:
                    assert payload["decode_retraces_after_warmup"] == 0, (
                        layout, n, payload["decode_retraces_after_warmup"])
                sweep[layout][n] = payload
            base, fused = sweep[layout][1], sweep[layout][8]
            assert (fused["host_overhead_per_token_s"]
                    < base["host_overhead_per_token_s"]), (
                layout, base["host_overhead_per_token_s"],
                fused["host_overhead_per_token_s"])
        overhead = {
            layout: {
                f"n{n}": {
                    "host_overhead_per_token_s":
                        p["host_overhead_per_token_s"],
                    "modeled_host_overhead_s": p["modeled_host_overhead_s"],
                    "measured_tok_s": p["measured_tok_s"],
                    "tokens_generated": p["tokens_generated"],
                    "decode_retraces_after_warmup":
                        p["decode_retraces_after_warmup"],
                }
                for n, p in by_n.items()
            }
            for layout, by_n in sweep.items()
        }
        path = write_json_artifact(
            "loadgen_host_overhead", overhead,
            dirpath=args.out_dir or None, kind="loadgen",
        )
        print(f"[loadgen] wrote {path}")
        print("[loadgen] smoke OK (dense + paged, N in {1, 8})")
    else:
        run_one(args, args.kv_layout)


if __name__ == "__main__":
    main()
