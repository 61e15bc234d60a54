"""Tests of the serving benchmark.  They run on the CPU at tiny widths:
``tiny_cell`` gives a cell of the real harness with the shapes cut down
and a small mix, so the whole run (set-up, window, reference) takes
seconds."""
from __future__ import annotations

import copy
import os
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}

TINY_MIX = {
    "sessions": {"clients": 6, "requests_per_client": 16, "warmup_s": 1,
                 "max_context": 160, "think_s": {"dist": "exponential",
                                                 "mean": 0.05},
                 "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                            "min": 4, "max": 24, "int": True},
                 "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                            "min": 4, "max": 16, "int": True},
                 "sessions_tokens": 32},
    "short": {"rate": 20.0, "warmup_s": 1, "max_context": 64,
              "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 4, "max": 32, "int": True},
              "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 4, "max": 32, "int": True}}
}


# a closed loop of plain requests, for the expert-parallel test cell
TINY_BATCH = {"why": "test", "loop": "closed", "schedule_seed": 5,
              "clients": 8, "requests_per_client": 8, "warmup_s": 1,
              "max_context": 64,
              "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                         "min": 4, "max": 32, "int": True},
              "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 4, "max": 32, "int": True}}

# a sparse-expert configuration at tiny widths (the program's mixtral
# config: 8 experts top-2, sliding window, untied head), served on a mesh
TINY_MOE = {"model_type": "mixtral", "num_local_experts": 8,
            "num_experts_per_tok": 2, "rms_norm_eps": 1e-05,
            "rope_theta": 1000000.0, "tie_word_embeddings": False,
            "sliding_window": 64, "torch_dtype": "bfloat16",
            "program_config": "mixtral-8x7b", "reference": "decoder",
            "deployment": {"chips": 4, "slots": 4, "page_size": 8,
                           "pool_pages": 33, "prefix_cache": False}}


def tiny(cell_name: str, chips: int = 1, limit: float = 1e-3,
         committed: bool = False):
    """The cell ``cell_name`` of BENCHMARK.json at tiny widths, judged by
    ``limit`` on the widest gap, or with ``committed`` by the cell's own
    limits (``limits/<cell>.json``)."""
    import harness
    cell = copy.deepcopy(harness.load_cell(cell_name))
    cell.hp.update(TINY)
    dep = cell.hp["deployment"]
    dep["slots"] = 4
    dep["pool_pages"] = 1 + 4 * 40
    mix = dict(TINY_MIX["short" if cell.mix["loop"] == "open"
                        else "sessions"])
    n_sys = mix.pop("sessions_tokens", None)
    cell.mix.update(mix)
    if n_sys:
        cell.mix["sessions"] = copy.deepcopy(cell.mix["sessions"])
        cell.mix["sessions"]["system_prompt"]["tokens"] = n_sys
    cell.chips = chips
    if not committed:
        cell.limits = {"max_logit_gap": {"limit": limit}}
    return cell


def tiny_moe(chips: int = 4, limit: float = 1e-3):
    """A tiny sparse-expert cell on ``chips`` devices, closed loop."""
    import harness
    hp = dict(copy.deepcopy(TINY_MOE), **TINY)
    return harness.Cell("tiny-moe", chips, hp, copy.deepcopy(TINY_BATCH),
                        [], [], {"max_logit_gap": {"limit": limit}})


@pytest.fixture
def tiny_cell():
    return tiny
