"""What a traced run hands to the per-layer metric readers.

Host spans and lifecycle events come from the program's tracer (on
``time.perf_counter``, the serve loop's clock); counters from
``PagedBackend.prefix_stats()``; the work the window asked of the device is
rebuilt from the serve loop's records (``driver.py``); device times come
from the profiler trace (``trace_reduce``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from bench.driver import Window
from bench.trace_reduce import Trace
from bench.work import Stack


@dataclasses.dataclass
class Ctx:
    window: Window
    t0: float                       # perf_counter at the window's start
    max_batch: int
    page_size: int
    num_pages: int
    shape: Stack                    # the model's layer groups (``work.py``)
    peak: Dict
    spans: List                     # program tracer SpanRecords in the window
    events: Dict[int, List]         # uid -> [(event, t, args)]
    prefix_before: Dict
    prefix_after: Dict
    pool_used: List[float]          # pool pages held / usable, after each step
    admissions: List[Tuple[int, int, int]]   # (uid, prompt tokens, reused pages)
    trace: Optional[Trace] = None

    def ticks(self) -> List[List[int]]:
        """Live KV lengths of every decode tick the window ran: in a step
        that gave a request k new tokens, it was live in k ticks, the
        tick that returned its j-th token holding prompt + j + 1 tokens."""
        count: Dict[int, int] = {}
        plen = {r.uid: r.prompt_len for r in self.window.records
                if r.uid is not None}
        out: List[List[int]] = []
        for st in self.window.steps:
            new = st["new"]
            n = max(new.values(), default=0)
            ticks: List[List[int]] = [[] for _ in range(n)]
            for uid, k in new.items():
                if uid not in plen:
                    continue
                j0 = count.get(uid, 0)
                for t in range(k):
                    ticks[t].append(plen[uid] + j0 + t + 1)
                count[uid] = j0 + k
            out.extend(ticks)
        return out

    def prefills(self) -> List[Tuple[int, int]]:
        """(prefix tokens reused, tail tokens prefilled) per admission."""
        return [(m * self.page_size, n - m * self.page_size)
                for _, n, m in self.admissions]

    def span_list(self, name: str) -> List:
        return [s for s in self.spans if s.name == name]
