"""Tiny sizes of the benchmark's configurations and traffic mixes, so that
a whole run can be driven on the CPU."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: Tiny sizes of each configuration and traffic mix, by name.
TINY = {
    ("configs", "stablelm-1.6b"): {
        "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 512, "torch_dtype": "float32",
        "deployment": {"n_slots": 4, "max_seq": 192}},
    ("traffic", "steps-512-f32"): {"grid": 32},
    ("traffic", "steps-256-bf16"): {"grid": 32},
    # short requests, so that a short window on a busy CPU still finishes
    # many; outputs longer than prompts, as in the mix
    ("traffic", "sharegpt"): {"backlog": 8, "prompt_mean": 4,
                              "output_mean": 12},
}

#: Window of the tiny serving runs, in seconds.
SERVE_SECONDS = 2.0


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) else v
    return out
