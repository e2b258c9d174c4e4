"""A checkout-shaped directory with tiny cells, for CPU runs of the harness.

The cells, configurations, mixes and limits are written as files into a
temporary root; the harness code is the repository's own and finds them by
name, as it finds the real ones. Beside the repository's families the root
holds a test-only one, ``windowed_global`` (``windowed_global.py`` here), and
a cell ``tiny-mixed.session`` whose configuration names it.
"""

from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

CONF = {
    "name": "tiny", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "max_position_embeddings": 4096,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "sliding_window": 64,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "engine": {"kv_layout": "paged", "page_size": 16, "steps_per_sync": 4,
               "batch_prefills": False},
}
SESSION = {
    "loop": "closed", "sessions": 3, "max_batch": 4, "max_context": 256,
    "prompt_buckets": [16, 32, 64, 128, 256], "prefix_room_pages": 16,
    "stagger_s": 0.2,
    "history": {"median": 64, "sigma": 0.3, "min": 48, "max": 96},
    "message": {"median": 12, "sigma": 0.5, "min": 8, "max": 24},
    "reply": {"median": 10, "sigma": 0.3, "min": 8, "max": 16},
    "greedy_share": 1.0, "temperature": 0.0, "top_p": 1.0,
}
CHAT = {
    "loop": "open", "rate": 4.0, "max_batch": 4, "max_context": 128,
    "prompt_buckets": [16, 32, 64, 96], "prefix_room_pages": 16,
    "system_prompts": {"count": 2, "length": 32, "weights": [0.6, 0.4], "share": 0.8},
    "user": {"median": 16, "sigma": 0.6, "min": 4, "max": 48},
    "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "greedy_share": 0.5, "temperature": 0.7, "top_p": 0.95,
}
#: Three sliding layers (window 24, rope 1e4) then one global (rope 1e6),
#: each pattern position its own weights: a stack the dense family refuses.
MIXED_CONF = dict(
    CONF, name="tiny-mixed", family="windowed_global", num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=24, rope_local_base_freq=10000.0, rope_theta=1000000.0)
MIXED_CELL = "tiny-mixed.session"
#: A limit for the tiny cells: sound CPU runs read about 0.02 (bf16 over
#: two layers), the planted faults and the float8 control read 0.2 or more.
LIMITS = {"max_logit_gap": 0.1, "min_tokens_compared": 8}


def make_root(root: pathlib.Path) -> pathlib.Path:
    """``tiny.session``, ``tiny.chat`` and ``tiny-mixed.session`` under
    ``root``, with the repository's metrics and families and BENCHMARK.json's
    metric list."""
    bench = root / "bench"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "families"):
        shutil.copytree(REPO / "bench" / d, bench / d, dirs_exist_ok=True)
    shutil.copy(pathlib.Path(__file__).with_name("windowed_global.py"),
                bench / "families" / "windowed_global.py")
    (bench / "configs" / "tiny.json").write_text(json.dumps(CONF))
    (bench / "configs" / "tiny-mixed.json").write_text(json.dumps(MIXED_CONF))
    (bench / "traffic" / "tiny-session.json").write_text(json.dumps(SESSION))
    (bench / "traffic" / "tiny-chat.json").write_text(json.dumps(CHAT))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    rename = {w["name"]: "tiny." + ("session" if w["traffic"] == "session-4k" else "chat")
              for w in spec["workloads"]}
    for cell in [*rename.values(), MIXED_CELL]:
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
    spec["workloads"] = [
        {"name": rename[w["name"]], "config": "tiny",
         "traffic": "tiny-session" if w["traffic"] == "session-4k" else "tiny-chat",
         "chips": 1, "why": "tiny"}
        for w in spec["workloads"]
    ] + [{"name": MIXED_CELL, "config": "tiny-mixed", "traffic": "tiny-session",
          "chips": 1, "why": "tiny, windowed and global layers"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
            if "tiny.session" in m["workloads"]:
                m["workloads"].append(MIXED_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
