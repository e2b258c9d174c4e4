"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3 [--control 3]

In one process (set-up is long), for each seed: serve a window exactly as
``run.py`` does, then read the widest logit gap of the served tokens against
the reference and, for the first ``--control`` seeds, the widest gap of the
float8 control's first choices at the same positions, put through the
same ``check.decide`` and ``check.passed`` that decide ``correct``
(``control_correct`` has to read false). One JSON line per seed. The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run
from run import CompileCount, ROOT, check, device_info, log, registry, run_cell  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    import jax

    from repro import compat

    cell = registry.load_cell(args.workload, ROOT)
    device = device_info(jax, cell.chips)
    run._DEVICE = f"{device['platform']} {device['kind']} x{device['count']}"
    compat.enable_compilation_cache()
    compiles = CompileCount(jax)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                       device=device, t_start=t, compiles=compiles,
                       control=i < args.control)
        line = {"seed": seed, **out.readings, "correct": out.result["correct"],
                "checks": out.result["checks"],
                "metrics": {k: v["value"] for k, v in out.result["metrics"].items()},
                "memory_peak_bytes": out.result["device"]["memory_peak_bytes"]}
        if out.control is not None:
            line["control_checks"] = out.control
            line["control_correct"] = check.passed(out.control)
        print(json.dumps(line), flush=True)
        log(f"seed {seed} done in {time.perf_counter() - t:.1f} s")
        del out
        gc.collect()
    sys.exit(0)


if __name__ == "__main__":
    main()
