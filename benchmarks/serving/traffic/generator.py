"""The one traffic generator: a mix is a JSON file of parameters beside
this module, read by name (``traffic/<name>.json``).

The token ids of every prompt, the shared system prompts and the
synthetic answers are drawn from ``--seed``.  The schedule (lengths,
session structure, think times and arrival gaps) is drawn from the mix's
own ``schedule_seed``, so every seed offers the same work: runs on two
seeds measured 6.7% apart in throughput and 14% in the TTFT tail when the
seed drew the schedule too, and two runs of one seed not at all.  The
program under test only ever receives the generated prompts.

Draws are stratified: a stream of ``n`` values of one distribution is made
of blocks of ``block`` values, each block holding the same ``block``
quantiles in a drawn order, so a window holds the distribution and not a
lucky part of it.  In a closed loop a block spans the clients: the j-th
request of every client together hold one block.  An open loop's blocks
follow arrival order (``block`` in the mix, default 64).

Two loops:

* ``closed``: ``clients`` users, each sending its next request only after
  the previous one finished plus a think time.  With ``sessions`` set, a
  user runs chat sessions: every turn's prompt is the previous prompt,
  a synthetic answer of the previous turn's ``max_new`` tokens, and a new
  message, so the trace does not depend on what the
  model emits.  A session ends after its drawn number of turns or when
  the next turn would pass ``max_context``; the user then starts a new
  one.  The shared system prompts are served from the prefix cache from
  the first turn on: the harness caches them during set-up, as a server
  that has been running holds them.
* ``open``: requests arrive at fixed offsets, Poisson at ``rate``.  The
  gaps are unit exponentials divided by the rate, so the sizes and the
  order of requests do not depend on the rate.

The arithmetic of arrivals, percentiles, TTFT and TPOT follows
``repro/serve/traffic.py`` and ``repro/serve/telemetry.py::percentile``
(copied, so that a change to the program cannot move the yardstick).
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional, Sequence

import numpy as np

HERE = Path(__file__).resolve().parent
BLOCK = 64                     # stratification block (values per quantile set)


def load_mix(name: str, root: Optional[Path] = None) -> Dict:
    """The parameters of mix ``name`` (``traffic/<name>.json``)."""
    path = (root or HERE) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


# --------------------------------------------------------------------------
# stratified draws
# --------------------------------------------------------------------------
def _quantile(dist: Dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        return dist["median"] * np.exp(dist["sigma"] * z)
    if kind == "exponential":
        return -dist["mean"] * np.log1p(-u)
    if kind == "geometric":          # support 1, 2, ...; mean 1/p
        p = 1.0 / dist["mean"]
        return np.ceil(np.log1p(-u) / math.log1p(-p))
    if kind == "zipf":               # support 1..n, P(k) ~ k^-s
        n, s = dist["n"], dist["s"]
        w = np.arange(1, n + 1, dtype=np.float64) ** -s
        cdf = np.cumsum(w / w.sum())
        return np.searchsorted(cdf, u, side="right") + 1.0
    raise ValueError(f"unknown distribution {kind!r}")


def draws(dist: Dict, n: int, rng: np.random.Generator,
          block: int = BLOCK) -> np.ndarray:
    """``n`` stratified draws of ``dist`` in blocks of ``block``, clipped to
    its ``min``/``max`` and rounded when ``dist`` says ``"int": true``."""
    u = (np.arange(block) + 0.5) / block
    q = _quantile(dist, u)
    blocks = -(-n // block)
    out = np.concatenate([q[rng.permutation(block)] for _ in range(blocks)])
    out = out[:n]
    if "min" in dist or "max" in dist:
        out = np.clip(out, dist.get("min", -np.inf), dist.get("max", np.inf))
    if dist.get("int"):
        out = np.rint(out).astype(np.int64)
    return out


def per_client(dist: Dict, n: int, clients: int, seed: int,
               name: str) -> np.ndarray:
    """[clients, n] draws: value j of every client together form one block
    of quantiles, so each round of requests across the clients offers the
    whole distribution."""
    rng = _client_rng(seed, -1, name)
    return draws(dist, n * clients, rng, block=clients).reshape(n, clients).T


# --------------------------------------------------------------------------
# the schedule
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Req:
    """One request as the generator emits it.  ``arrival`` is the offset
    from the start of the traffic (open loop); ``think`` is the pause
    after the client's previous request finished (closed loop)."""
    client: int
    index: int                 # position in the client's stream
    prompt: np.ndarray         # int32 token ids
    max_new: int
    arrival: Optional[float] = None
    think: float = 0.0
    session: int = -1
    turn: int = 0


def _tokens(rng: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, int(n), dtype=np.int64).astype(np.int32)


def _client_rng(seed: int, client: int, stream: str) -> np.random.Generator:
    tag = sum(ord(c) * 131 ** i for i, c in enumerate(stream)) % (2 ** 31)
    return np.random.default_rng([int(seed) % (2 ** 63), client + 1, tag])


def _session_stream(mix: Dict, vocab: int, seed: int, client: int,
                    n_requests: int, systems: List[np.ndarray],
                    sizes: Dict[str, np.ndarray]) -> List[Req]:
    """A user's stream of chat turns (closed loop, ``sessions`` set)."""
    max_ctx = mix["max_context"]
    r_tok = _client_rng(seed, client, "tokens")
    msgs, answers, thinks = (sizes[k][client]
                             for k in ("prompt", "output", "think_s"))
    turns = sizes["turns"][client]
    which = sizes["system_prompt"][client] - 1
    n = len(msgs)
    out: List[Req] = []
    i = s = 0
    while len(out) < n_requests:
        n_turns = int(turns[s % n])
        history = systems[int(which[s % n])]
        for t in range(n_turns):
            msg = _tokens(r_tok, vocab, msgs[i % n])
            ans = int(answers[i % n])
            prompt = np.concatenate([history, msg])
            if len(prompt) + ans > max_ctx:
                i += 1
                break
            out.append(Req(client, len(out), prompt, ans,
                           think=float(thinks[i % n]), session=s, turn=t))
            i += 1
            history = np.concatenate([prompt, _tokens(r_tok, vocab, ans)])
            if len(out) >= n_requests:
                break
        s += 1
    return out


def _plain_stream(vocab: int, seed: int, client: int, n_requests: int,
                  sizes: Dict[str, np.ndarray]) -> List[Req]:
    r_tok = _client_rng(seed, client, "tokens")
    lens, outs = sizes["prompt"][client], sizes["output"][client]
    thinks = (sizes["think_s"][client] if "think_s" in sizes
              else np.zeros(n_requests))
    return [Req(client, j, _tokens(r_tok, vocab, lens[j]), int(outs[j]),
                think=float(thinks[j])) for j in range(n_requests)]


def system_prompts(mix: Dict, vocab: int, seed: int) -> List[np.ndarray]:
    """The shared system prompts of a session mix (none otherwise)."""
    if not mix.get("sessions"):
        return []
    sp = mix["sessions"]["system_prompt"]
    r = _client_rng(seed, -1, "system")
    return [_tokens(r, vocab, sp["tokens"]) for _ in range(sp["n"])]


def closed_streams(mix: Dict, vocab: int, seed: int,
                   n_requests: int) -> List[List[Req]]:
    """Per client, its first ``n_requests`` requests."""
    assert mix["loop"] == "closed"
    systems = system_prompts(mix, vocab, seed)
    clients = mix["clients"]
    ses = mix.get("sessions")
    n = n_requests * 2 if ses else n_requests
    dists = {k: mix[k] for k in ("prompt", "output", "think_s") if k in mix}
    if ses:
        dists.update(turns=ses["turns"], system_prompt=ses["system_prompt"])
    sizes = {k: per_client(d, n, clients, mix["schedule_seed"], k)
             for k, d in dists.items()}
    if ses:
        return [_session_stream(mix, vocab, seed, c, n_requests, systems,
                                sizes) for c in range(clients)]
    return [_plain_stream(vocab, seed, c, n_requests, sizes)
            for c in range(clients)]


def open_schedule(mix: Dict, vocab: int, seed: int, rate: float,
                  n_requests: int) -> List[Req]:
    """``n_requests`` open-loop requests with arrival offsets at ``rate``.
    The sizes and the unit gaps are drawn independently of ``rate``.  The
    gaps of a block are scaled to a mean of exactly 1, so block ``b``
    arrives in ``[b, b + 1) * block_seconds(mix, rate)``, its first
    request at the start."""
    assert mix["loop"] == "open" and rate > 0
    block = mix.get("block", BLOCK)
    plan = mix["schedule_seed"]
    sizes = {k: draws(mix[k], n_requests, _client_rng(plan, 0, k), block)[None]
             for k in ("prompt", "output")}
    reqs = _plain_stream(vocab, seed, 0, n_requests, sizes)
    r_gap = _client_rng(plan, 0, "arrivals")
    unit = {"dist": "exponential", "mean": 1.0}
    scale = block / _quantile(unit, (np.arange(block) + 0.5) / block).sum()
    gaps = draws(unit, n_requests, r_gap, block) * scale
    t = (np.cumsum(gaps) - gaps) / rate
    for j, r in enumerate(reqs):
        r.client, r.arrival = j, float(t[j])
    return reqs


def block_seconds(mix: Dict, rate: float) -> float:
    """How long one block of an open loop's arrivals lasts."""
    return mix.get("block", BLOCK) / rate


def warmup_seconds(mix: Dict) -> float:
    """Traffic before the window: ``warmup_s``, or for an open loop
    ``warmup_blocks`` whole blocks, so the window starts with a block."""
    if "warmup_blocks" in mix:
        return mix["warmup_blocks"] * block_seconds(mix, mix["rate"])
    return float(mix.get("warmup_s", 0.0))


def schedule_bytes(reqs: Sequence[Req]) -> bytes:
    """A canonical serialization (for the determinism test)."""
    parts = []
    for r in reqs:
        parts.append(np.asarray([r.client, r.index, r.max_new, r.session,
                                 r.turn], np.int64).tobytes())
        parts.append(np.asarray([r.arrival if r.arrival is not None
                                 else -1.0, r.think], np.float64).tobytes())
        parts.append(np.asarray(r.prompt, np.int32).tobytes())
    return b"".join(parts)


# --------------------------------------------------------------------------
# latency arithmetic (copied from repro/serve/traffic.py and telemetry.py)
# --------------------------------------------------------------------------
def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile on the sorted sample (numpy's
    default rule)."""
    assert 0.0 <= q <= 100.0
    s = sorted(float(x) for x in xs)
    if not s:
        return float("nan")
    if len(s) == 1:
        return s[0]
    pos = q / 100.0 * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (pos - lo) * (s[hi] - s[lo])


@dataclasses.dataclass
class Timing:
    """Host-clock timestamps of one request (seconds, one clock)."""
    arrival: float                     # scheduled send time
    first: Optional[float] = None
    last: Optional[float] = None
    n_tokens: int = 0
    finish: Optional[float] = None

    def ttft(self, close: float) -> float:
        """From scheduled arrival to first token; a request with no first
        token at ``close`` counts with the time it has waited so far."""
        if self.first is None or self.first > close:
            return close - self.arrival
        return self.first - self.arrival

    def tpot(self) -> float:
        """(last - first) / (n - 1): the rate a streaming client sees."""
        if self.n_tokens <= 1:
            return 0.0
        return (self.last - self.first) / (self.n_tokens - 1)
