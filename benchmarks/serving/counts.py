"""Work counts and peaks: the operations and the least bytes a served
token needs, from the configuration's shapes at its published dtype, and
the chip's peaks (``peaks.json``, keyed by ``device_kind``).

The counts are of the algorithm, whatever implements it: the weights a
step must read once at the published dtype, the keys and values of each
slot's live context only (capped by the window), and the operations of
the experts each token is routed to.  A gather of whole rows, a float32
engine or every expert computed for every token are the program's cost,
not the algorithm's, so they lower a roofline share and never raise it.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

HERE = Path(__file__).resolve().parent
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak row of ``device_kind``; an unknown device is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def _d(hp: Dict) -> Dict:
    d, H = hp["hidden_size"], hp["num_attention_heads"]
    return dict(d=d, L=hp["num_hidden_layers"], H=H,
                KV=hp["num_key_value_heads"],
                hd=hp.get("head_dim") or d // H,
                ff=hp["intermediate_size"], V=hp["vocab_size"],
                E=hp.get("num_local_experts", 0),
                K=hp.get("num_experts_per_tok", 0),
                tied=bool(hp.get("tie_word_embeddings", False)),
                window=int(hp.get("sliding_window") or 0),
                qk_norm=hp["model_type"] == "qwen3")


def published_bytes(hp: Dict) -> int:
    """Bytes per value at the configuration's (published) dtype."""
    return DTYPE_BYTES[hp["torch_dtype"]]


def attn_proj_params(hp: Dict) -> int:
    m = _d(hp)
    return m["d"] * (m["H"] + 2 * m["KV"]) * m["hd"] + m["H"] * m["hd"] * m["d"]


def expert_params(hp: Dict) -> int:
    """One expert's (or the dense MLP's) SwiGLU weights."""
    m = _d(hp)
    return 3 * m["d"] * m["ff"]


def matmul_params_per_token(hp: Dict) -> int:
    """Weights one token multiplies by: projections, the experts it is
    routed to (or the MLP), the router, the output head."""
    m = _d(hp)
    ffn = (m["K"] * expert_params(hp) + m["d"] * m["E"] if m["E"]
           else expert_params(hp))
    return m["L"] * (attn_proj_params(hp) + ffn) + m["d"] * m["V"]


def attended(hp: Dict, ctx: int) -> int:
    """Keys a token at context length ``ctx`` (itself included) reads."""
    w = _d(hp)["window"]
    return min(ctx, w) if w else ctx


def flops_per_token(hp: Dict, ctx: int) -> int:
    """Operations of one token at context ``ctx``: 2 per multiply-add of
    the weights, plus QK^T and PV over the attended keys."""
    m = _d(hp)
    attn = 2 * 2 * m["H"] * m["hd"] * attended(hp, ctx)
    return 2 * matmul_params_per_token(hp) + m["L"] * attn


def kv_bytes_per_token(hp: Dict) -> int:
    """K and V of one position in every layer, at the published dtype."""
    m = _d(hp)
    return 2 * m["L"] * m["KV"] * m["hd"] * published_bytes(hp)


def step_weight_bytes(hp: Dict, n_tokens: int) -> int:
    """Weights one step over ``n_tokens`` tokens must read once: every
    layer's projections and norms, the experts that can receive a token
    (at most ``K * n_tokens`` of ``E``), the output head, and only the
    embedding rows of those tokens where the head is not tied."""
    m = _d(hp)
    b = published_bytes(hp)
    norms = 2 * m["d"] + (2 * m["hd"] if m["qk_norm"] else 0)
    if m["E"]:
        hit = min(m["E"], m["K"] * n_tokens)
        ffn = hit * expert_params(hp) + m["d"] * m["E"]
    else:
        ffn = expert_params(hp)
    layers = m["L"] * (attn_proj_params(hp) + ffn + norms)
    head = m["d"] * m["V"] + m["d"]
    rows = 0 if m["tied"] else n_tokens * m["d"]
    return (layers + head + rows) * b


def step_work(hp: Dict, ctxs: Sequence[int]) -> Dict[str, int]:
    """Operations and least bytes of one decode step over slots whose
    tokens sit at context lengths ``ctxs`` (keys attended, own included)."""
    flops = sum(flops_per_token(hp, c) for c in ctxs)
    kv = sum(attended(hp, c) for c in ctxs) * kv_bytes_per_token(hp)
    return {"flops": flops,
            "bytes": step_weight_bytes(hp, len(ctxs)) + kv}


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float],
                  chips: int) -> Dict[str, float]:
    """The larger of the two bounds over ``chips`` chips, and which binds."""
    tf = flops / (chips * peak["bf16_flops"])
    tb = nbytes / (chips * peak["hbm_bytes_per_s"])
    return {"seconds": max(tf, tb), "flops_s": tf, "bytes_s": tb,
            "bound": "compute" if tf >= tb else "memory"}
