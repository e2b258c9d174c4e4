"""Prove the serving path runs on a TPU: gemma2-2b at full width, one chip.

    python chip_smoke.py [--seed N]      # one chip (TPU v5e or another known kind)
    python chip_smoke.py --four-chips    # mesh=4 vs mesh=None, four chips

Phases, in one process (a chip belongs to one process):

  a. device   — JAX must see a TPU; otherwise exit 2 before anything else.
  b. model    — ``gemma2-2b`` unchanged, random params from ``--seed``.
  c. kernels  — last-position prefill logits (512 tokens) and one paged
                decode step (8 rows), each on the Pallas plan and on the
                plain ``ref`` impl, compared under the bf16 tolerance below.
  d. serving  — ``LLMEngine(kv_layout="paged", steps_per_sync=4)`` serves
                8 requests (64-128 prompt tokens, half sharing a 32-token
                prefix, 32 greedy tokens each), twice; every plan the engine
                resolved is a compiled Pallas plan and the second batch
                traces no new decode program.
  e. result   — the last stdout line is the JSON contract
                ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only the sharded path and what it is compared with:
the same requests through ``mesh=None`` and through a 4-device mesh
(replicated params, head-sharded KV); greedy tokens must be equal.

Tolerance. bf16 keeps 8 significant bits (unit roundoff u = 2**-8). The
Pallas and ``ref`` paths differ only in how each layer's attention output
is accumulated before it is rounded to bf16, so each of the L = 26 layers
adds at most about one rounding of relative size u, independently; over the
stack they add like a random walk, sqrt(L) * u ~ 0.02 relative. The check
allows four times that on the relative L2 error of the logits:
``4 * sqrt(L) * 2**-8`` = 0.0797.

Compilation uses JAX's persistent cache (``JAX_COMPILATION_CACHE_DIR`` if
set, else ``.jax_cache`` in the checkout). The compile seconds on the lines
marked informational sum XLA compile (or persistent-cache retrieval) time.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import math
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

PREFILL_LEN = 512
DECODE_ROWS, DECODE_MAX_PAGES, PAGE = 8, 32, 16
N_REQUESTS, PREFIX_LEN, NEW_TOKENS = 8, 32, 32


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {msg}")


def device_info(jax, want: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees platform {d.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    check(len(devs) >= want, f"needs {want} TPU devices, JAX sees {len(devs)}")
    log(f"device kind={d.device_kind!r} count={len(devs)} "
        f"jax={jax.__version__} libtpu={importlib.metadata.version('libtpu')}")
    return {"platform": d.platform, "kind": d.device_kind, "count": want}


class CompileClock:
    """Sums XLA compile (or persistent-cache retrieval) seconds."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def peak_gb(jax) -> float:
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"] / 1e9


def rel_l2(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def prompts(cfg, seed: int):
    """8 prompts of 64-128 tokens; the odd ones share a 32-token prefix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab, PREFIX_LEN)
    out = []
    for i in range(N_REQUESTS):
        n = 64 + (64 * i) // (N_REQUESTS - 1)
        if i % 2:
            out.append(np.concatenate(
                [system, rng.integers(0, cfg.vocab, n - PREFIX_LEN)]))
        else:
            out.append(rng.integers(0, cfg.vocab, n))
    return out


def serve(engine, batch, SamplingParams):
    """Queue ``batch``, drive the engine to completion, return uid->tokens."""
    for p in batch:
        engine.add_request(prompt=p, sampling=SamplingParams(
            temperature=0.0, max_tokens=NEW_TOKENS))
    outs = engine.generate()
    check(len(outs) == len(batch), f"{len(outs)} of {len(batch)} finished")
    for o in outs:
        check(o.finished and o.finish_reason is not None,
              f"request {o.uid} ended without a finish_reason")
    return {o.uid: [int(t) for t in o.tokens] for o in outs}


def kernels_vs_reference(jax, jnp, cfg, params, seed: int, tol: float):
    """Phase c: Pallas plan vs the plain ``ref`` impl at full width."""
    import numpy as np

    from repro.kernels import plan as plan_lib
    from repro.models import transformer

    ref_cfg = dataclasses.replace(cfg, attn_impl="ref")
    rng = np.random.default_rng(seed + 1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, PREFILL_LEN)), jnp.int32)

    def prefill(c):
        return jax.jit(lambda p, t: transformer.prefill(
            p, c, t, cache_len=PREFILL_LEN)[0])

    with plan_lib.record_plans() as plans:
        got = prefill(cfg)(params, tokens).block_until_ready()
    want = prefill(ref_cfg)(params, tokens).block_until_ready()
    check(plans and all(p.impl == "pallas" and not p.interpret for p in plans),
          f"prefill ran non-Pallas plans: {plans}")
    err = rel_l2(got, want)
    log(f"prefill {PREFILL_LEN} tok: logits rel L2 err {err:.6g} "
        f"(tol {tol:.4g}), max abs {float(jnp.max(jnp.abs(got - want))):.6g}, "
        f"argmax equal {bool(jnp.argmax(got) == jnp.argmax(want))}")
    check(bool(jnp.all(jnp.isfinite(got))), "prefill logits not finite")
    check(err <= tol, f"prefill logits error {err} > {tol}")

    # One paged decode step over seeded page contents.
    num_pages = DECODE_ROWS * DECODE_MAX_PAGES + 1
    caches = transformer.init_paged_caches(params, cfg, num_pages, PAGE)
    leaves, tree = jax.tree.flatten(caches)
    key = jax.random.PRNGKey(seed + 2)
    caches = jax.tree.unflatten(tree, [
        jax.random.normal(jax.random.fold_in(key, i), x.shape, x.dtype)
        for i, x in enumerate(leaves)
    ])
    perm = 1 + rng.permutation(num_pages - 1)
    page_table = jnp.asarray(
        perm.reshape(DECODE_ROWS, DECODE_MAX_PAGES), jnp.int32)
    lengths = jnp.asarray(
        rng.integers(64, DECODE_MAX_PAGES * PAGE, DECODE_ROWS), jnp.int32)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, DECODE_ROWS), jnp.int32)

    def decode(c):
        return jax.jit(lambda p, t, kv, ln, pt: transformer.decode_step(
            p, c, t, kv, ln, page_table=pt)[0])

    with plan_lib.record_plans() as plans:
        got = decode(cfg)(params, tok, caches, lengths, page_table)
    want = decode(ref_cfg)(params, tok, caches, lengths, page_table)
    check(plans and all(p.impl == "pallas" and not p.interpret for p in plans),
          f"decode ran non-Pallas plans: {plans}")
    err = rel_l2(got, want)
    log(f"paged decode {DECODE_ROWS} rows: logits rel L2 err {err:.6g} "
        f"(tol {tol:.4g})")
    check(bool(jnp.all(jnp.isfinite(got))), "decode logits not finite")
    check(err <= tol, f"decode logits error {err} > {tol}")


def one_chip(args, jax, jnp, clock) -> dict:
    from repro.configs import registry
    from repro.kernels import plan as plan_lib
    from repro.models import transformer
    from repro.serving import LLMEngine, SamplingParams

    cfg = registry.get_config("gemma2-2b")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        transformer.init_model(jax.random.PRNGKey(args.seed), cfg))
    log(f"gemma2-2b init: {cfg.param_count() / 1e9:.3f}B params, "
        f"{time.perf_counter() - t0:.1f} s, peak HBM {peak_gb(jax):.3f} GB")

    tol = 4 * math.sqrt(cfg.n_layers) * 2.0 ** -8
    kernels_vs_reference(jax, jnp, cfg, params, args.seed, tol)

    batch = prompts(cfg, args.seed)
    with plan_lib.record_plans() as plans:
        engine = LLMEngine(cfg, params, kv_layout="paged", steps_per_sync=4)
        c0, t0 = clock.seconds, time.perf_counter()
        first = serve(engine, batch, SamplingParams)
        t_first = time.perf_counter() - t0
        traces = engine.backend.stats["decode_traces"]
        t0 = time.perf_counter()
        second = serve(engine, batch, SamplingParams)
        t_second = time.perf_counter() - t0
    check(engine.backend.stats["decode_traces"] == traces,
          f"second batch retraced decode: {traces} -> "
          f"{engine.backend.stats['decode_traces']}")
    phases = {p.phase for p in plans}
    check(phases == {plan_lib.PREFILL, plan_lib.EXTEND, plan_lib.DECODE},
          f"engine resolved phases {sorted(phases)}")
    bad = [p for p in plans if p.impl != "pallas" or p.interpret]
    check(not bad, f"engine resolved non-Pallas plans: {bad}")
    check(engine.backend.stats["extend_prefills"] > 0, "no extend prefill ran")
    engine.close()
    n_tok = N_REQUESTS * NEW_TOKENS
    log(f"served {len(first)} + {len(second)} requests; decode traces "
        f"{traces}; plans {len(plans)} ({sorted(phases)})")
    log(f"informational: batch 1 {t_first:.2f} s (compile "
        f"{clock.seconds - c0:.2f} s), batch 2 {t_second:.2f} s, "
        f"{n_tok / t_second:.1f} tok/s")
    log(f"peak HBM at end {peak_gb(jax):.3f} GB")


def four_chips(args, jax, jnp, clock) -> None:
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import registry
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer
    from repro.serving import LLMEngine, SamplingParams

    cfg = registry.get_config("gemma2-2b")
    batch = prompts(cfg, args.seed)
    key = jax.random.PRNGKey(args.seed)

    params = transformer.init_model(key, cfg)
    engine = LLMEngine(cfg, params, kv_layout="paged", steps_per_sync=4)
    single = serve(engine, batch, SamplingParams)
    engine.close()
    del engine, params
    gc.collect()
    log(f"mesh=None served {len(single)} requests")

    # Build the replicated params in place: a copy of device 0's params
    # beside them would not fit its 16 GB.
    mesh = make_serving_mesh(4)
    params = jax.jit(
        transformer.init_model_eager, static_argnums=1,
        out_shardings=NamedSharding(mesh, PartitionSpec()),
    )(key, cfg)
    engine = LLMEngine(cfg, params, kv_layout="paged", steps_per_sync=4,
                       mesh=mesh)
    pages = engine.backend.caches["scanned"][0]["attn"]["k_pages"]
    n_dev = len(pages.sharding.device_set)
    check(n_dev == 4, f"page pool spans {n_dev} devices, not 4")
    sharded = serve(engine, batch, SamplingParams)
    engine.close()
    same = [single[u] == sharded[u] for u in sorted(single)]
    log(f"mesh=4 served {len(sharded)} requests; pool sharding "
        f"{pages.sharding.spec} over {n_dev} devices; greedy tokens equal "
        f"{sum(same)}/{len(same)}")
    check(all(same), "mesh=4 greedy tokens differ from mesh=None")
    log(f"peak HBM device 0 {peak_gb(jax):.3f} GB; compile {clock.seconds:.2f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only mesh=4 against mesh=None on four chips")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    want = 4 if args.four_chips else 1
    device = device_info(jax, want)
    sys.path.insert(0, str(SRC))
    from repro import compat

    log(f"compile cache {compat.enable_compilation_cache()}")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args, jax, jnp, clock)
    log(f"informational: total {time.perf_counter() - t0:.1f} s, compile "
        f"{clock.seconds:.2f} s, persistent-cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
