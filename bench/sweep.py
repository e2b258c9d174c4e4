"""Find an open-loop cell's knee: the highest offered rate at which the
backlog does not grow over the window.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates 1.5,2,2.5

One process; for each rate, the cell's mix at that rate is served exactly as
``run.py`` serves it. Per rate one JSON line: the requests due, started
(first token) and finished in the window, the TTFT tail, and the number
waiting or running after each step in the first and last fifth of the
window. A backlog that grows shows as the last fifth holding clearly more
than the first. The cell's rate is then set, by hand, to 0.8 of the knee.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import run
from run import CompileCount, ROOT, device_info, driver, registry, run_cell  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax

    from repro import compat

    cell = registry.load_cell(args.workload, ROOT)
    device = device_info(jax, cell.chips)
    run._DEVICE = f"{device['platform']} {device['kind']} x{device['count']}"
    compat.enable_compilation_cache()
    compiles = CompileCount(jax)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["rate"] = rate
        out = run_cell(cell, seed=args.seed, seconds=args.seconds, trace=False,
                       device=device, t_start=time.perf_counter(),
                       compiles=compiles)
        w = out.window
        backlog = []
        for st in w.steps:
            t = st["t1"]
            backlog.append((t, sum(1 for r in w.records
                                   if r.due <= t and not (r.finished and r.last <= t))))
        fifth = args.seconds / 5
        early = [b for t, b in backlog if t <= fifth]
        late = [b for t, b in backlog if t >= args.seconds - fifth]
        ttft = driver.ttft_values(w)
        print(json.dumps({
            "rate": rate, "due": len(ttft),
            "started": sum(1 for r in w.records if r.first is not None),
            "finished": sum(1 for r in w.records if r.finished),
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
            "backlog_first_fifth": float(np.mean(early)) if early else None,
            "backlog_last_fifth": float(np.mean(late)) if late else None,
            "metrics": {k: v["value"] for k, v in out.result["metrics"].items()},
            "correct": out.result["correct"],
        }), flush=True)


if __name__ == "__main__":
    main()
