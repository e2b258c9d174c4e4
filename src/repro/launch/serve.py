"""Serving launcher: the LLMEngine facade over a synthetic request stream.

The KV layout is a flag, not a class choice: ``--kv-layout auto`` lets the
plan layer's NUMA decode model pick dense stripes vs the paged pool (and
falls back to dense for models the paged subsystem cannot hold);
``dense`` / ``paged`` pin it. Per-request sampling flags drive the
on-device batched sampler. SchedulerStats print at exit.

Examples:
  python -m repro.launch.serve --arch llama3-8b --smoke --requests 16 \
      --max-new-tokens 12
  python -m repro.launch.serve --arch llama3-8b --smoke --kv-layout paged \
      --temperature 0.8 --top-k 40 --top-p 0.95
  python -m repro.launch.serve --arch llama3-8b --smoke --mesh 4 \
      --steps-per-sync auto
  python -m repro.launch.serve --arch llama3-8b --smoke --kv-dtype int8 \
      --host-pool-bytes 1048576
"""

from __future__ import annotations

import argparse
import dataclasses
import math

import jax
import numpy as np

from repro import compat
from repro.configs import registry
from repro.models import transformer
from repro.serving import LLMEngine, Request, SamplingParams


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--kv-layout", choices=("auto", "dense", "paged"),
                    default="auto")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode rows (max_batch)")
    ap.add_argument("--cache-len", type=int, default=512)
    ap.add_argument("--num-pages", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of system prompt shared by every request "
                         "(exercises paged prefix sharing)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard serving over N devices (1-D 'model' mesh, "
                         "head-sharded KV; 0 = single-device)")
    ap.add_argument("--steps-per-sync", default="1",
                    help="fused decode ticks per host sync: an int, or "
                         "'auto' to let the scheduler pick from the live "
                         "batch's modeled tick time")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8", "fp8"),
                    default="fp32",
                    help="paged pool storage dtype; int8/fp8 store "
                         "quantized codes + per-page-per-head scales")
    ap.add_argument("--host-pool-bytes", type=int, default=0,
                    help="host-DRAM KV tier budget in bytes (0 = off): "
                         "cold pages demote host-side under pool "
                         "pressure and promote back on prefix match")
    args = ap.parse_args(argv)
    compat.enable_compilation_cache()

    cfg = (registry.get_smoke_config if args.smoke else registry.get_config)(args.arch)
    if args.mesh > 1 and args.smoke and cfg.n_kv_heads % args.mesh:
        # Smoke configs keep tiny head counts; widen KV heads to the
        # smallest multiple the mesh divides so the head-sharded pool has
        # an even split (smoke-only — real configs must divide as-is).
        factor = args.mesh // math.gcd(cfg.n_kv_heads, args.mesh)
        cfg = dataclasses.replace(
            cfg, n_kv_heads=cfg.n_kv_heads * factor,
            n_heads=cfg.n_heads * factor,
        )
        print(f"smoke mesh fit: widened heads x{factor} -> "
              f"Hq={cfg.n_heads} Hkv={cfg.n_kv_heads}")
    steps = (args.steps_per_sync if args.steps_per_sync == "auto"
             else int(args.steps_per_sync))
    params = transformer.init_model(jax.random.PRNGKey(args.seed), cfg)
    engine = LLMEngine(
        cfg, params,
        kv_layout=args.kv_layout,
        max_batch=args.slots,
        cache_len=args.cache_len,
        num_pages=args.num_pages,
        page_size=args.page_size,
        prompt_buckets=(args.prompt_len, 2 * args.prompt_len),
        mesh=args.mesh if args.mesh > 1 else None,
        steps_per_sync=steps,
        kv_dtype=args.kv_dtype,
        host_pool_bytes=args.host_pool_bytes or None,
    )
    print(f"kv_layout={engine.kv_layout} (requested {args.kv_layout}) "
          f"devices={engine.backend.num_devices}")
    rng = np.random.default_rng(args.seed)
    shape = (args.prompt_len,) if cfg.num_codebooks == 1 else (
        args.prompt_len, cfg.num_codebooks)
    system = rng.integers(1, cfg.vocab, size=(args.shared_prefix,)) \
        if args.shared_prefix else None
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(1, cfg.vocab, size=shape)
        if system is not None and cfg.num_codebooks == 1:
            prompt = np.concatenate(
                [system, prompt[: args.prompt_len - args.shared_prefix]]
            )
        reqs.append(Request(
            uid=i, prompt=prompt,
            sampling=SamplingParams(
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, max_tokens=args.max_new_tokens,
            ),
        ))
    results = engine.generate(reqs)
    for r in sorted(results, key=lambda r: r.uid)[:4]:
        toks = [int(np.asarray(t).reshape(-1)[0]) for t in r.tokens]
        print(f"  uid={r.uid} prompt_len={r.prompt_len} "
              f"finish={r.finish_reason} out={toks}")
    print(engine.stats().summary())


if __name__ == "__main__":
    main()
