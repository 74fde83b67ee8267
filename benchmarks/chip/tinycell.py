"""Small cells for the CPU tests: a copy of this benchmark with tiny
configurations of both model paths (Qwen3-style: qk-norm, tied head;
Mistral-style: no qk-norm, untied head, heads x head_dim != width) in
float32, tiny traffic mixes and their limits, in a temporary checkout whose
`src` points at the repository's."""
from __future__ import annotations

import copy
import json
import os
import shutil
from typing import Dict, Optional

from chipbench import BENCH_DIR, CHECKOUT, spec

SERVING = {"kv_layout": "paged", "prefill_mode": "bulk",
           "decode_kernel": "pallas", "batch_slots": 4, "cache_len": 96,
           "block_size": 16, "pool_blocks": 49}
QWEN = {"name": "tiny-qwen", "source": "test", "model_type": "qwen3",
        "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
        "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "tie_word_embeddings": True, "torch_dtype": "float32",
        "hidden_act": "silu", "qk_norm": True, "serving": SERVING}
MISTRAL = dict(QWEN, name="tiny-mistral", model_type="mistral",
               hidden_size=160, rms_norm_eps=1e-5,
               tie_word_embeddings=False, qk_norm=False)
CLOSED = {"loop": "closed", "in_flight_per_slot": 1.5,
          "prompt_tokens": {"median": 24, "sigma": 0.7, "min": 8, "max": 64},
          "output_tokens": {"median": 8, "sigma": 0.7, "min": 4, "max": 24},
          "lead_in_s": 0.0, "greedy": True}
OPEN = dict(CLOSED, loop="open", rate_per_s=2.0, lead_in_s=0.5)
LIMITS = {"check": {"max_requests": 5},
          "limits": {"served_logit_gap_max": {"max": 1e-3},
                     "tokens_compared": {"min": 8},
                     "replica_failures": {"max": 0}}}


def make(tmp: str, archs: Optional[Dict[str, str]] = None,
         qwen_model_type: str = "qwen3") -> str:
    """A checkout under tmp holding this benchmark and two tiny cells:
    tiny-qwen.closed and tiny-mistral.open. `archs` maps a model_type to
    the source of an archs/<model_type>.py added to the copy;
    tiny-qwen's configuration names `qwen_model_type`. Returns its path."""
    root = os.path.join(tmp, "checkout")
    bench = os.path.join(root, os.path.relpath(BENCH_DIR, CHECKOUT))
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, source in (archs or {}).items():
        with open(os.path.join(bench, "archs", name + ".py"), "w") as f:
            f.write(source)
    os.symlink(os.path.join(CHECKOUT, "src"), os.path.join(root, "src"))
    doc = copy.deepcopy(spec.load_benchmark(CHECKOUT))
    for c in (dict(QWEN, model_type=qwen_model_type), MISTRAL):
        rel = os.path.join(os.path.relpath(bench, root), "configs",
                           c["name"] + ".json")
        _write(os.path.join(root, rel), c)
        doc["configs"].append({"name": c["name"], "source": "test",
                               "file": rel, "reduced": [], "why": "test"})
    _write(os.path.join(bench, "traffic", "closed.json"), CLOSED)
    _write(os.path.join(bench, "traffic", "open.json"), OPEN)
    for name, cfg, traffic in (("tiny-qwen.closed", "tiny-qwen", "closed"),
                               ("tiny-mistral.open", "tiny-mistral", "open")):
        _write(os.path.join(bench, "cells", name + ".json"), LIMITS)
        doc["workloads"].append({"name": name, "config": cfg,
                                 "traffic": traffic, "chips": 1,
                                 "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] == "ttft_p90_ms":
            m["workloads"] = ["tiny-mistral.open"]
        elif m["name"] != "setup_s":
            m["workloads"] = ["tiny-qwen.closed"]
    for m in doc["per_layer"]:
        m["workloads"] = []
    _write(os.path.join(root, "BENCHMARK.json"), doc)
    return root


def bench_dir(root: str) -> str:
    return os.path.join(root, os.path.relpath(BENCH_DIR, CHECKOUT))


def _write(path: str, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
