"""A cell small enough for the CPU: the two benchmark configurations'
architecture (dense, RMSNorm, RoPE, SwiGLU, MHA or GQA with QKV bias) at
toy widths, under short-pause tool traffic."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.spec import Cell  # noqa: E402

CONFIG = {
    "source": "tiny", "num_hidden_layers": 2, "hidden_size": 128,
    "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "attention_bias": True,
    "tie_word_embeddings": False, "vocab_size": 512,
    "serving": {"dtype": "bfloat16", "kv_dtype": None, "page_size": 16,
                "max_model_len": 256, "n_pages": 40,
                "warm": {"max_batch": 4, "max_tokens": 128}},
}

TRAFFIC = {
    "rate_per_s": 3.0, "ramp_s": 2.0, "shape_seed": 1,
    "kinds": ["math", "qa", "ve"],
    "tool_calls": {"dist": "uniform", "lo": 1, "hi": 2},
    "prompt": {"dist": "lognormal", "lo": 16, "hi": 64},
    "stretch": {"dist": "lognormal", "lo": 4, "hi": 12},
    "final": {"dist": "lognormal", "lo": 4, "hi": 12},
    "max_context": 240,
}


def cell(limits=None, **serving) -> Cell:
    conf = copy.deepcopy(CONFIG)
    conf["serving"].update(serving)
    return Cell(name="tiny.tools", chips=1, config_name="tiny",
                config=conf, traffic_name="tiny",
                traffic=copy.deepcopy(TRAFFIC),
                limits=limits or {"max_gap": 0.1},
                end_to_end=[{"name": n} for n in
                            ("tokens_per_s", "turn_p90_s", "itl_p99_s",
                             "setup_s")],
                per_layer=[])
