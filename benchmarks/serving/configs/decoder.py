"""The plain reference of the decoder configurations beside this file
(``qwen3``: GQA, RoPE, RMSNorm, q/k norm, SwiGLU; ``mixtral``: GQA, RoPE,
RMSNorm, sliding-window attention, 8 experts top-2), and the weights that
both the program and the reference are given.

Weights are drawn from the seed in the published layout, one layer at a
time from ``fold_in(key, layer)``, so the reference can make any layer
again without holding the others.  :func:`program_params` makes the same
values in the program's layout in one jitted call on the device, in the
shardings the program serves them in.

Both configurations are published in bfloat16, so every weight is drawn
as a bfloat16 value (held in float32 where the program serves float32).

The reference imports nothing of the program.  It is written from the
published model descriptions (Hugging Face ``modeling_qwen3.py`` and
``modeling_mixtral.py``): straightforward ``jax.numpy`` in float32 with
every product at ``Precision.HIGHEST``.  ``mode="int8"`` gives the
control, one step below the bfloat16 the configurations state: every
product with a weight (projections, experts, router, output head) in
int8, weights quantized per output channel and activations per row,
symmetric, accumulated in int32, as W8A8 serving computes them; the rest
as in the reference.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NORM_NOISE = 0.1         # norm weights are 1 + NORM_NOISE * N(0, 1)
MODES = ("float32", "int8")


def dims(hp: Dict) -> Dict:
    d, H = hp["hidden_size"], hp["num_attention_heads"]
    return dict(
        d=d, L=hp["num_hidden_layers"], H=H,
        KV=hp["num_key_value_heads"], hd=hp.get("head_dim") or d // H,
        ff=hp["intermediate_size"], V=hp["vocab_size"],
        E=hp.get("num_local_experts", 0), K=hp.get("num_experts_per_tok", 0),
        tied=bool(hp.get("tie_word_embeddings", False)),
        eps=float(hp["rms_norm_eps"]), theta=float(hp["rope_theta"]),
        window=int(hp.get("sliding_window") or 0),
        qk_norm=hp["model_type"] == "qwen3")


# --------------------------------------------------------------------------
# weights (published layout; x @ W orientation)
# --------------------------------------------------------------------------
def _bf16(x):
    """The published dtype's value, held in float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _normal(key, shape, scale):
    return _bf16(jax.random.normal(key, shape, jnp.float32) * scale)


def _norm(key, n):
    return _bf16(1.0 + NORM_NOISE * jax.random.normal(key, (n,), jnp.float32))


def layer_weights(hp: Dict, key) -> Dict:
    """Layer weights from the layer's own key (``fold_in(key, layer)``)."""
    m = dims(hp)
    d, H, KV, hd, ff, E = m["d"], m["H"], m["KV"], m["hd"], m["ff"], m["E"]
    ks = jax.random.split(key, 12)
    w = {"input_norm": _norm(ks[0], d), "post_norm": _norm(ks[1], d),
         "q": _normal(ks[2], (d, H * hd), d ** -0.5),
         "k": _normal(ks[3], (d, KV * hd), d ** -0.5),
         "v": _normal(ks[4], (d, KV * hd), d ** -0.5),
         "o": _normal(ks[5], (H * hd, d), (H * hd) ** -0.5)}
    if m["qk_norm"]:
        w["q_norm"] = _norm(ks[6], hd)
        w["k_norm"] = _norm(ks[7], hd)
    if E:
        w["router"] = _normal(ks[8], (d, E), d ** -0.5)
        w["gate"] = _normal(ks[9], (E, d, ff), d ** -0.5)
        w["up"] = _normal(ks[10], (E, d, ff), d ** -0.5)
        w["down"] = _normal(ks[11], (E, ff, d), ff ** -0.5)
    else:
        w["gate"] = _normal(ks[9], (d, ff), d ** -0.5)
        w["up"] = _normal(ks[10], (d, ff), d ** -0.5)
        w["down"] = _normal(ks[11], (ff, d), ff ** -0.5)
    return w


def outer_weights(hp: Dict, key) -> Dict:
    """Embedding, final norm and (untied) output head."""
    m = dims(hp)
    ks = jax.random.split(jax.random.fold_in(key, 2 ** 31 - 1), 3)
    w = {"embed": _normal(ks[0], (m["V"], m["d"]), m["d"] ** -0.5),
         "final_norm": _norm(ks[1], m["d"])}
    if not m["tied"]:
        w["lm_head"] = _normal(ks[2], (m["d"], m["V"]), m["d"] ** -0.5)
    return w


def _layer_key(key, layer):
    return jax.random.fold_in(key, layer)


def _to_program_layer(hp: Dict, w: Dict) -> Dict:
    """One layer in the program's layout: norms as offsets from 1 (the
    program multiplies by ``1 + scale``), SwiGLU as w1 (gate), w3 (up),
    w2 (down)."""
    m = dims(hp)
    attn = {"wq": w["q"], "wk": w["k"], "wv": w["v"], "wo": w["o"]}
    if m["qk_norm"]:
        attn["q_norm"] = w["q_norm"] - 1.0
        attn["k_norm"] = w["k_norm"] - 1.0
    ffn = {"w1": w["gate"], "w3": w["up"], "w2": w["down"]}
    p = {"ln1": w["input_norm"] - 1.0, "attn": attn,
         "ln2": w["post_norm"] - 1.0}
    if m["E"]:
        ffn["router"] = w["router"]
        p["moe"] = ffn
    else:
        p["mlp"] = ffn
    return p


def program_tree(hp: Dict, key) -> Dict:
    """All weights in the program's layout (traceable; see
    :func:`program_params`)."""
    m = dims(hp)
    layers = jax.vmap(lambda l: _to_program_layer(
        hp, layer_weights(hp, _layer_key(key, l))))(jnp.arange(m["L"]))
    o = outer_weights(hp, key)
    p = {"embed": o["embed"], "final_norm": o["final_norm"] - 1.0,
         "stages": [[layers]]}
    if not m["tied"]:
        p["lm_head"] = o["lm_head"]
    return p


def program_params(hp: Dict, seed: int, shardings=None):
    """The program's weights from ``seed``, made on the device in one
    jitted call (in ``shardings`` when given)."""
    key = seed_key(seed)
    fn = jax.jit(partial(program_tree, hp), out_shardings=shardings)
    return fn(key)


def program_shapes(hp: Dict):
    return jax.eval_shape(partial(program_tree, hp), seed_key(0))


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (two 32-bit words)."""
    s = int(seed) % (2 ** 63)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


# --------------------------------------------------------------------------
# the reference forward pass, one layer at a time
# --------------------------------------------------------------------------
def _int8(x, axis):
    """Symmetric int8 along ``axis``: (values, scale)."""
    s = jnp.maximum(jnp.abs(x).max(axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _dot(a, b, mode):
    """a [..., k] @ b [k, n]: float32 at HIGHEST, or W8A8 int8."""
    if mode == "float32":
        return jnp.matmul(a, b, precision=HI)
    qa, sa = _int8(a, -1)
    qb, sb = _int8(b, 0)
    acc = jnp.matmul(qa, qb, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sb


def _rmsnorm(x, w, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * w


def _rope(x, theta):
    """x [L, heads, hd]: rotate-half RoPE at positions 0..L-1."""
    L, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_forward(hp: Dict, w: Dict, x, mode="float32"):
    """One decoder layer over one whole sequence x [L, d] (causal); the
    products with weights in ``mode``, the rest in float32 at HIGHEST."""
    m = dims(hp)
    H, KV, hd = m["H"], m["KV"], m["hd"]
    L = x.shape[0]
    h = _rmsnorm(x, w["input_norm"], m["eps"])
    q = _dot(h, w["q"], mode).reshape(L, H, hd)
    k = _dot(h, w["k"], mode).reshape(L, KV, hd)
    v = _dot(h, w["v"], mode).reshape(L, KV, hd)
    if m["qk_norm"]:
        q = _rmsnorm(q, w["q_norm"], m["eps"])
        k = _rmsnorm(k, w["k_norm"], m["eps"])
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    g = H // KV
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    qi = jnp.arange(L)[:, None]
    ki = jnp.arange(L)[None, :]
    ok = ki <= qi
    if m["window"]:
        ok &= qi - ki < m["window"]
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    x = x + _dot(o.reshape(L, H * hd), w["o"], mode)
    h = _rmsnorm(x, w["post_norm"], m["eps"])
    if m["E"]:
        # top-k of the router's softmax, renormalised over the k chosen
        # (the same as softmax over the top-k logits)
        logits = _dot(h, w["router"], mode)
        top_v, top_i = lax.top_k(logits, m["K"])
        top_w = jax.nn.softmax(top_v, axis=-1)
        comb = jnp.zeros((L, m["E"]), jnp.float32).at[
            jnp.arange(L)[:, None], top_i].set(top_w)

        def expert(acc, e):
            a = jax.nn.silu(_dot(h, w["gate"][e], mode)) * _dot(
                h, w["up"][e], mode)
            return acc + comb[:, e][:, None] * _dot(a, w["down"][e],
                                                    mode), None

        y, _ = lax.scan(expert, jnp.zeros(x.shape, jnp.float32),
                        jnp.arange(m["E"]))
    else:
        a = jax.nn.silu(_dot(h, w["gate"], mode)) * _dot(h, w["up"], mode)
        y = _dot(a, w["down"], mode)
    return x + y


def embed(hp: Dict, outer: Dict, tokens):
    return outer["embed"][tokens]


def logit_gaps(hp: Dict, outer: Dict, x_ref, x_ctl, targets):
    """At every position: how far below the reference's best logit lie
    ``targets`` (the served tokens) and, when ``x_ctl`` is given, the
    control's own first choice.  x [L, d] are the last layer's outputs."""
    m = dims(hp)
    head = outer["embed"].T if m["tied"] else outer["lm_head"]
    h = _rmsnorm(x_ref, outer["final_norm"], m["eps"])
    ref = _dot(h, head, "float32")
    best = ref.max(-1)
    served = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    out = {"served_gap": best - served}
    if x_ctl is not None:
        hc = _rmsnorm(x_ctl, outer["final_norm"], m["eps"])
        pick = jnp.argmax(_dot(hc, head, "int8"), -1)
        out["control_gap"] = best - jnp.take_along_axis(
            ref, pick[:, None], -1)[:, 0]
    return out


class Reference:
    """Runs the reference (and, when asked, the control) over a batch of
    whole sequences, layer by layer: each layer's weights are made from
    the seed once, applied to every sequence, and dropped."""

    def __init__(self, hp: Dict, seed: int, length: int, device=None):
        self.hp, self.m = hp, dims(hp)
        self.key = seed_key(seed)
        self.length = length
        self.device = device or jax.devices()[0]
        self._layer_w = jax.jit(partial(layer_weights, hp))
        self._outer = jax.jit(partial(outer_weights, hp))
        self._fwd = {mode: jax.jit(partial(layer_forward, hp, mode=mode))
                     for mode in MODES}
        self._embed = jax.jit(partial(embed, hp))
        self._gaps = jax.jit(partial(logit_gaps, hp))

    def run(self, seqs, control: bool = False):
        """``seqs``: list of (tokens int32 [n], first served position p0):
        positions p0 .. n-2 predict tokens p0+1 .. n-1.  Returns, per
        sequence, numpy arrays of gaps at those positions."""
        import numpy as np
        L = self.length
        with jax.default_device(self.device):
            outer = self._outer(self.key)
            toks = []
            for t, _ in seqs:
                pad = np.zeros((L,), np.int32)
                pad[:len(t)] = t
                toks.append(jnp.asarray(pad))
            xs = [self._embed(outer, t) for t in toks]
            xc = list(xs) if control else None
            for layer in range(self.m["L"]):
                w = self._layer_w(_layer_key(self.key, layer))
                xs = [self._fwd["float32"](w, x) for x in xs]
                if control:
                    xc = [self._fwd["int8"](w, x) for x in xc]
                del w
            out = []
            for i, (t, p0) in enumerate(seqs):
                tgt = jnp.asarray(np.concatenate(
                    [t[1:], np.zeros((L - len(t) + 1,), np.int32)]))
                g = self._gaps(outer, xs[i], xc[i] if control else None, tgt)
                n = len(t)
                out.append({k: np.asarray(v)[p0:n - 1] for k, v in g.items()})
            return out
