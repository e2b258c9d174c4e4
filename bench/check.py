"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample of the
greedy requests the window finished (drawn from the seed, the longest always
in it) is run through the plain reference, prompt and served tokens
together. At each served position the gap is the reference's best logit
minus the reference's logit of the served token; the run is correct when
the widest gap is within the cell's limit (``bench/limits/<cell>.json``) and
enough tokens were compared. A greedy token from a sound program can only
lose to the reference's best by rounding; a wrong cache, kernel or token
loses by much more.

The control reads the same positions with the reference in float8 (the step
below the configuration's bfloat16): the gap of the token the lower
precision puts first. Its readings go through the same ``decide`` and
``passed`` as the program's, and have to come out as not correct.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference
from bench.driver import Record

#: Served tokens the sample aims at, and the most requests it takes.
TARGET_TOKENS = 384
MAX_REQUESTS = 6


def sample(records: Sequence[Record], seed: int) -> List[Record]:
    """Finished greedy requests to compare: the longest, then others in a
    seeded order until about ``TARGET_TOKENS`` served tokens."""
    done = [r for r in records
            if r.finished and r.req.greedy and len(r.served) and not r.failed]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.served), r.req.key))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed) % (1 << 64), 77]).permutation(len(rest))
    picked = [longest]
    n = len(longest.served)
    for i in order:
        if n >= TARGET_TOKENS or len(picked) >= MAX_REQUESTS:
            break
        picked.append(rest[i])
        n += len(rest[i].served)
    return picked


def _inputs(r: Record):
    served = np.asarray(r.served, np.int32)
    tokens = np.concatenate([np.asarray(r.req.prompt, np.int32), served])
    pos = r.prompt_len - 1 + np.arange(len(served))
    return tokens, pos, served


def readings(params, cell, picked: Sequence[Record], control: bool = False
             ) -> Tuple[Dict[str, float], Optional[Dict[str, float]]]:
    """The numbers compared for the served tokens over ``picked``, by the
    reference of ``cell``'s family, and with ``control`` the same numbers
    for the float8 reference's first choices at the same positions
    (``None`` without it)."""
    conf, family = cell.conf, cell.family
    gap, ctrl, n = 0.0, 0.0, 0
    for r in picked:
        tokens, pos, served = _inputs(r)
        ref = reference.logits(params, conf, tokens, pos, family=family)
        gap = max(gap, reference.widest_gap(ref, served))
        n += len(served)
        if control:
            low = reference.logits(params, conf, tokens, pos, quantized=True,
                                   family=family)
            ctrl = max(ctrl, reference.widest_gap(ref, low.argmax(axis=1)))
    out = {"max_logit_gap": gap, "tokens_compared": float(n)}
    if not control:
        return out, None
    return out, {"max_logit_gap": ctrl, "tokens_compared": float(n)}


def decide(read: Dict[str, float], limits: Dict) -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit."""
    return {
        "max_logit_gap": {"value": read.get("max_logit_gap", float("inf")),
                          "limit": float(limits["max_logit_gap"])},
        "tokens_compared": {"value": read.get("tokens_compared", 0.0),
                            "limit": float(limits["min_tokens_compared"])},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return (checks["max_logit_gap"]["value"] <= checks["max_logit_gap"]["limit"]
            and checks["tokens_compared"]["value"] >= checks["tokens_compared"]["limit"])
