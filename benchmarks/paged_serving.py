"""Paged serving benchmark: prefix sharing + NUMA page placement A/B.

Drives ``LLMEngine(kv_layout="paged")`` (smoke model, CPU-runnable) over a
mixed-length request trace with a shared system prompt, then scores the *final*
page tables under both placement policies with the three model layers:

  * ``cache.layout.decode_page_traffic``  — exact enumerated traffic,
  * ``core.cache_sim.simulate_paged_decode`` — event-driven LRU replay,
  * ``core.perf_model.estimate_paged_decode`` / ``estimate_dense_decode``
    — the O(1) analytic forms ``kernels.ops.resolve_kv_layout`` ranks with.

Reports prefix-cache hit rate (acceptance: > 0 on this trace) and modeled
HBM/fabric traffic for head-aligned vs interleaved placement, plus the
dense-stripe baseline the paged pool replaces, and the modeled
paged-vs-gather cost of the extend-phase prefill the PR-3 kernel replaces.

Run: PYTHONPATH=src python -m benchmarks.paged_serving
  --smoke: CI mode — a short trace that must route prefix-extension
  prefill through the paged Pallas prefill kernel (interpret mode on CPU
  runners; asserts the non-fallback path was taken), skipping the full
  placement sweep.
Artifacts: artifacts/benchmarks/paged_serving.json
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks import common
from repro.cache import layout
from repro.configs import registry
from repro.core import cache_sim, numa, perf_model
from repro.kernels import ops as kernel_ops
from repro.models import transformer
from repro.serving import LLMEngine, Request

PAGE_SIZE = 16
NUM_PAGES = 160
TOPOS = {"mi300x": numa.MI300X, "tpu_v5p_megacore": numa.TPU_V5P_MEGACORE}


def build_trace(cfg, rng, n_requests=12, system_len=48):
    """Mixed-length trace: most requests share a system prompt."""
    system = rng.integers(1, cfg.vocab, size=(system_len,))
    reqs = []
    for i in range(n_requests):
        tail_len = int(rng.integers(2, 40))
        tail = rng.integers(1, cfg.vocab, size=(tail_len,))
        prompt = np.concatenate([system, tail]) if i % 4 else tail
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(3, 10))))
    return reqs


def capture_peak_tables(engine):
    """Snapshot live page tables at the engine's fullest decode tick."""
    peak = {"pages": -1, "tables": [], "lengths": []}
    backend = engine.backend
    orig_step = engine.step

    def step():
        live = [
            (list(backend.seqs[r].pages.pages), int(backend.lengths[r]) + 1)
            for r in range(backend.rows)
            if backend.active[r] and backend.seqs[r] is not None
        ]
        total = sum(-(-ln // backend.page_size) for _, ln in live)
        if total > peak["pages"]:
            peak.update(pages=total, tables=[t for t, _ in live],
                        lengths=[ln for _, ln in live])
        return orig_step()

    engine.step = step
    return peak


def smoke():
    """CI smoke: drive the paged engine over a prefix-sharing trace and
    assert the extend phase ran through the paged Pallas prefill kernel
    (plan impl == "pallas"; interpret mode on CPU) — the non-fallback
    route — with outputs completing for every request; then exercise a
    plan-chosen ``num_splits > 1`` split-K decode (interpret mode) and
    check it against the oracle."""
    import jax.numpy as jnp

    from repro.kernels import plan as plan_lib
    from repro.kernels import ref

    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    engine = LLMEngine(
        cfg, params, kv_layout="paged", num_pages=96, page_size=PAGE_SIZE,
        max_batch=4, max_pages_per_seq=8, prompt_buckets=(16, 32, 64),
    )
    reqs = build_trace(cfg, rng, n_requests=6, system_len=32)
    results = engine.generate(reqs)
    stats = engine.backend.prefix_stats()
    assert len(results) == len(reqs), (len(results), len(reqs))
    assert stats["prefix_hit_rate"] > 0, "trace must exercise prefix sharing"
    assert stats["extend_prefills"] > 0, \
        "no request took the paged prefill kernel path"
    # The engine's extend plans must all be the kernel (no gather fallback).
    extend_keys = [k for k in engine.backend._prefill_p if k[1] > 0]
    assert extend_keys, "no extend-phase compilation recorded"
    for bucket, pages, rows in extend_keys:
        plan = plan_lib.plan_for_config(
            cfg,
            (rows, cfg.n_heads, cfg.n_kv_heads, bucket,
             pages * engine.backend.page_size + bucket, cfg.head_dim),
            phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED,
            page_size=engine.backend.page_size, prefix_pages=pages,
        )
        assert plan.impl == "pallas", plan
    new_tokens = sum(len(r.tokens) for r in results)
    print(
        f"[smoke] {len(results)} requests, {new_tokens} new tokens, "
        f"prefix hit rate {stats['prefix_hit_rate']:.2f}, "
        f"{int(stats['extend_prefills'])} extend prefills via "
        f"paged_flash_prefill (interpret={plan.interpret}), "
        f"{int(stats['batched_prefills'])} batched launches, "
        f"jit keys {sorted(engine.backend._prefill_p)}"
    )
    print(f"[smoke] {engine.stats().summary()}")

    # Split-K decode (PR 4): a long-context B x Hkv = 1 shape must resolve
    # to num_splits > 1 on the scoring topology, and the split kernel must
    # run (interpret mode on CPU runners) to oracle parity.
    b, hq, hkv, smax, hd = 1, 4, 1, 32768, 64
    splan = plan_lib.plan_attention(
        (b, hq, hkv, 1, smax, hd), phase=plan_lib.DECODE, backend="cpu",
        dtype_bytes=4, impl="pallas",
    )
    assert splan.num_splits > 1, splan
    assert splan.interpret, "CI smoke must exercise interpret mode"
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, hq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, smax, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, smax, hd), jnp.float32)
    lengths = jnp.asarray([smax - 3], jnp.int32)
    o = kernel_ops.decode_attention(q, kc, vc, lengths, plan=splan)
    o_ref = ref.decode_attention(q, kc, vc, lengths)
    err = float(jnp.max(jnp.abs(o - o_ref)))
    assert err < 2e-5, err
    print(
        f"[smoke] split-K decode: plan chose num_splits={splan.num_splits} "
        f"(chunk={splan.chunk}) for B*Hkv={b * hkv} at {smax} tokens; "
        f"kernel parity {err:.2e}"
    )

    # PR 6: the same long-context regime through the *paged* split kernel,
    # with the domain-purity access tracer auditing what the exported
    # BlockSpec index maps touch (repro.analysis.access_trace) — the
    # co-location claim fails CI here instead of silently invalidating the
    # modeled speedups. The page table is a random permutation of the
    # physical pool, so locality must come from the head-major layout, not
    # from accidentally-ordered page ids.
    from repro.analysis import access_trace
    from repro.kernels.paged_decode_attention import paged_flash_decode

    ps = 32
    pplan = plan_lib.plan_attention(
        (b, hq, hkv, 1, smax, hd), phase=plan_lib.DECODE,
        kv_layout=plan_lib.PAGED, page_size=ps, backend="cpu",
        dtype_bytes=4, impl="pallas",
    )
    assert pplan.num_splits > 1, pplan
    assert pplan.interpret, "CI smoke must exercise interpret mode"
    mp = smax // ps
    rng2 = np.random.default_rng(2)
    pt = rng2.permutation(np.arange(1, mp + 1)).reshape(1, mp).astype(np.int32)
    trace = access_trace.trace_plan(
        pplan, pt, [smax - 5], num_kv_heads=hkv, num_domains=2,
    ).assert_domain_local()
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q2 = jax.random.normal(ks[0], (b, hq, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (hkv, mp + 1, ps, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (hkv, mp + 1, ps, hd), jnp.float32)
    lengths2 = jnp.asarray([smax - 5], jnp.int32)
    o2 = paged_flash_decode(q2, kp, vp, jnp.asarray(pt), lengths2,
                            num_splits=pplan.num_splits, interpret=True)
    o2_ref = ref.paged_decode_attention(q2, kp, vp, jnp.asarray(pt), lengths2)
    err2 = float(jnp.max(jnp.abs(o2 - o2_ref)))
    assert err2 < 2e-5, err2
    print(
        f"[smoke] paged split-K: num_splits={pplan.num_splits} over {mp} "
        f"pages; access trace domain-local across {len(trace.cells)} grid "
        f"cells / {trace.live_pages} live page fetches; kernel parity "
        f"{err2:.2e}"
    )
    print("[smoke] OK")


def main():
    from repro.obs import Telemetry

    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    telemetry = Telemetry.create()
    engine = LLMEngine(
        cfg, params, kv_layout="paged", num_pages=NUM_PAGES,
        page_size=PAGE_SIZE, max_batch=6, max_pages_per_seq=8,
        prompt_buckets=(16, 32, 64, 96),
        telemetry=telemetry,
    )
    reqs = build_trace(cfg, rng)
    peak = capture_peak_tables(engine)
    results = engine.generate(reqs)
    stats = engine.backend.prefix_stats()
    assert len(results) == len(reqs)
    assert stats["prefix_hit_rate"] > 0, "trace must exercise prefix sharing"

    # The paper-scale attention geometry for the traffic models (the smoke
    # model's tiny heads would make domain counts degenerate).
    hkv, hd = 8, 128
    rows = []
    payload = {
        "page_size": PAGE_SIZE,
        "num_pages": NUM_PAGES,
        "requests": len(reqs),
        "new_tokens": sum(len(r.tokens) for r in results),
        "prefix": stats,
        "peak_tick": {"tables": peak["tables"], "lengths": peak["lengths"]},
        "model_geometry": {"num_kv_heads": hkv, "head_dim": hd},
        "placement": {},
    }
    for tname, topo in TOPOS.items():
        entry = {}
        for policy in layout.PAGE_POLICIES:
            traffic = layout.decode_page_traffic(
                peak["tables"], peak["lengths"], num_kv_heads=hkv,
                page_size=PAGE_SIZE, head_dim=hd, topo=topo, policy=policy)
            sim = cache_sim.simulate_paged_decode(
                peak["tables"], peak["lengths"], num_kv_heads=hkv,
                page_size=PAGE_SIZE, head_dim=hd, topo=topo, policy=policy)
            entry[policy] = {
                "total_bytes": traffic.total_bytes,
                "unique_bytes": traffic.unique_bytes,
                "local_fraction": traffic.local_fraction,
                "reuse_rate": traffic.reuse_rate,
                "sim_hit_rate": sim.hit_rate,
                "sim_hbm_bytes": sim.hbm_bytes,
                "sim_remote_bytes": sim.remote_bytes,
                "time_model_s": traffic.time(topo),
                "sim_elapsed_s": sim.elapsed,
            }
            rows.append({
                "topo": tname, "policy": policy,
                "local%": f"{100*traffic.local_fraction:.0f}",
                "reuse%": f"{100*traffic.reuse_rate:.0f}",
                "HBM MiB": f"{traffic.unique_bytes/2**20:.2f}",
                "remote MiB": f"{sim.remote_bytes/2**20:.2f}",
                "t_model us": f"{1e6*traffic.time(topo):.2f}",
            })
        # dense-stripe baseline + analytic layout ranking
        batch = len(peak["tables"])
        mean_len = int(np.mean(peak["lengths"])) if peak["lengths"] else 1
        capacity = engine.backend.cache_len
        dense = perf_model.estimate_dense_decode(
            batch=batch, num_q_heads=4 * hkv, num_kv_heads=hkv,
            capacity=capacity, head_dim=hd, dtype_bytes=2, topo=topo)
        entry["dense_baseline"] = {
            "capacity": capacity,
            "hbm_bytes": dense.hbm_bytes,
            "time_s": dense.time,
        }
        entry["resolved_layout"] = kernel_ops.resolve_kv_layout(
            (batch, 4 * hkv, hkv, mean_len, hd), capacity=capacity,
            page_size=PAGE_SIZE,
            # The CPU rehearsal topology carries the v5p megacore figures.
            backend="cpu" if "tpu" in tname else "gpu")
        payload["placement"][tname] = entry

    # Extend-phase prefill: modeled cost of the PR-3 paged prefill kernel
    # vs the gather-to-dense route it replaces, at this trace's mean
    # prefix/tail split.
    mean_prefix = int(
        PAGE_SIZE * stats["pages_reused"] / max(stats["extend_prefills"], 1)
    )
    extend_kw = dict(
        batch=1, num_q_heads=4 * hkv, num_kv_heads=hkv,
        prefix_len=max(mean_prefix, PAGE_SIZE), tail_len=32,
        page_size=PAGE_SIZE, head_dim=hd, dtype_bytes=2,
        topo=numa.MI300X,
    )
    paged_est = perf_model.estimate_extend_prefill(**extend_kw)
    gather_est = perf_model.estimate_extend_prefill(gather=True, **extend_kw)
    payload["extend_prefill"] = {
        "mean_prefix_len": extend_kw["prefix_len"],
        "paged_kernel_time_s": paged_est.time,
        "gather_dense_time_s": gather_est.time,
        "paged_vs_gather_ratio": gather_est.time / paged_est.time,
        "extend_prefills": stats["extend_prefills"],
        "resumed_tokens": stats["resumed_tokens"],
    }

    # Split-K decode (PR 4): plan-resolved num_splits and the modeled
    # decode-throughput win at long-context, small-batch shapes — the
    # occupancy regime (B*Hkv < num_domains) the split axis exists for.
    from repro.kernels import plan as plan_lib

    split_rows = []
    payload["split_k"] = {}
    long_ctx = 32768
    for b, hq_, hkv_ in [(1, 8, 1), (1, 32, 4), (1, 32, 8), (8, 32, 8)]:
        plan = plan_lib.plan_attention(
            (b, hq_, hkv_, 1, long_ctx, hd), phase=plan_lib.DECODE,
            backend="gpu", dtype_bytes=2,
        )
        est = perf_model.estimate_decode_splits(
            batch=b, num_q_heads=hq_, num_kv_heads=hkv_, seq_kv=long_ctx,
            granule=plan.chunk or 512, head_dim=hd, dtype_bytes=2,
            topo=numa.MI300X,
        )
        assert plan.num_splits == est.num_splits  # the plan IS the model
        payload["split_k"][f"b{b}_hq{hq_}_hkv{hkv_}"] = {
            "cells": b * hkv_,
            "num_domains": numa.MI300X.num_domains,
            "num_splits": plan.num_splits,
            "chunk": plan.chunk,
            "modeled_speedup": est.speedup,
            # Aggregate: one tick decodes one token per sequence.
            "tokens_per_s_one_pass": b / est.base_time,
            "tokens_per_s_split": b / est.time,
            "sweep": {str(s): t for s, t in est.times},
        }
        split_rows.append({
            "B": b, "Hq": hq_, "Hkv": hkv_,
            "cells": b * hkv_,
            "splits": plan.num_splits,
            "speedup": f"{est.speedup:.2f}x",
            "t_1 us": f"{1e6 * est.base_time:.1f}",
            "t_split us": f"{1e6 * est.time:.1f}",
        })
    lonely = payload["split_k"]["b1_hq8_hkv1"]
    assert lonely["num_splits"] > 1 and lonely["modeled_speedup"] > 1.0, \
        "B*Hkv < num_domains long-context decode must split"

    aligned = payload["placement"]["mi300x"][layout.HEAD_ALIGNED]
    naive = payload["placement"]["mi300x"][layout.INTERLEAVED]
    engine_stats = engine.stats()
    payload["measured"] = {
        "tokens_generated": engine_stats.tokens_generated,
        "measured_tok_s": engine_stats.measured_tok_s,
        "modeled_tok_s": engine_stats.modeled_tok_s,
        "decode_elapsed_s": engine_stats.decode_elapsed_s,
    }
    payload["headline"] = {
        "prefix_hit_rate": stats["prefix_hit_rate"],
        "aligned_vs_naive_time_ratio":
            naive["time_model_s"] / aligned["time_model_s"],
        "extend_paged_vs_gather_ratio":
            payload["extend_prefill"]["paged_vs_gather_ratio"],
        "split_k_speedup_b1_hkv1": lonely["modeled_speedup"],
        "split_k_num_splits_b1_hkv1": lonely["num_splits"],
    }

    print(common.render_table(
        "Paged decode tick: NUMA-aligned vs naive page placement",
        rows, ("topo", "policy", "local%", "reuse%", "HBM MiB",
               "remote MiB", "t_model us")))
    print(common.render_table(
        f"Split-K decode (mi300x, {long_ctx}-token context, plan-chosen "
        "splits)",
        split_rows, ("B", "Hq", "Hkv", "cells", "splits", "speedup",
                     "t_1 us", "t_split us")))
    print(f"\nprefix-cache hit rate: {stats['prefix_hit_rate']:.2f} "
          f"({int(stats['pages_reused'])}/{int(stats['prompt_pages'])} prompt pages)")
    print(f"aligned vs naive modeled speedup (mi300x): "
          f"{payload['headline']['aligned_vs_naive_time_ratio']:.2f}x")
    print(f"extend prefill, paged kernel vs gather+dense (modeled): "
          f"{payload['headline']['extend_paged_vs_gather_ratio']:.2f}x")
    print(f"split-K decode speedup at B*Hkv=1, {long_ctx} ctx (modeled): "
          f"{payload['headline']['split_k_speedup_b1_hkv1']:.2f}x "
          f"(num_splits={payload['headline']['split_k_num_splits_b1_hkv1']})")
    for tname in TOPOS:
        print(f"resolve_kv_layout[{tname}]: "
              f"{payload['placement'][tname]['resolved_layout']}")
    # The telemetry snapshot (step/flush/decode histograms, lifecycle
    # counters) rides in the artifact's "metrics" envelope slot.
    path = common.save_result("paged_serving", payload,
                              metrics=telemetry.metrics)
    print(f"\nsaved {path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI: short trace, assert the paged prefill kernel "
                         "path, skip the placement sweep")
    args = ap.parse_args()
    smoke() if args.smoke else main()
