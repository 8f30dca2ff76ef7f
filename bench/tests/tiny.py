"""A cell small enough for the CPU: the same drivers, files' layout and
checks as the chip's cells, at toy widths. Nothing here is a measurement."""
from __future__ import annotations

import copy
import json

from bench.common import BENCH, Cell

TINY_MODEL = {"num_layers": 4, "d_model": 256, "num_heads": 4,
              "num_kv_heads": 4, "d_ff": 512, "vocab_size": 32768,
              "qkv_bias": True, "mlp_gated": False, "tie_embeddings": True,
              "norm_eps": 1e-5, "rope_theta": 10000.0,
              "param_dtype": "bfloat16", "remat": "none"}


def serve_cell(limit: float) -> Cell:
    with open(BENCH / "configs" / "gpt2-xl-serve.json") as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config.update(name="tiny-serve", model=dict(TINY_MODEL))
    config["serve"].update(slots=4, capacity=96, page_size=8, num_pages=48,
                           prefill_buckets=[16, 32])
    mix = {"kind": "open_loop", "rate_per_s": 40.0,
           "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                          "min": 4, "max": 32},
           "output_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                          "min": 4, "max": 48},
           "check_requests": 16, "trace_seconds": 1, "warm_s": 1.5}
    return Cell(name="tiny-serve", chips=1, config=config, mix=mix,
                limits={"served_mean_gap": limit}, end_to_end=[],
                per_layer=[])
