"""The arithmetic of the metric readers (``metrics/<name>.py``), each of
which picks one of these.  A reader returns ``None`` where it finds
nothing to read, and the harness then leaves the metric out; a share of a
peak or a roofline is never reported as 0 for want of data."""
from __future__ import annotations

from typing import Optional

import counts
from traffic.generator import percentile


# -- end to end (host clock) ---------------------------------------------------
def output_tok_s(run) -> Optional[float]:
    """All output tokens delivered in the window / the window's seconds."""
    n = sum(k for t, k in run.delivered if run.in_window(t))
    return n / run.seconds if n else None


def ttft_p90(run) -> Optional[float]:
    """p90 over every request that arrived in the window, from scheduled
    arrival to first token; one with no first token at the close counts
    with the time it has waited so far."""
    xs = [tm.ttft(run.t_end) for tm in run.timings
          if run.in_window(tm.arrival)]
    return percentile(xs, 90) if xs else None


def tpot_p90(run) -> Optional[float]:
    """p90 over every request finished in the window of (last token -
    first token) / (n - 1)."""
    xs = [tm.tpot() for tm in run.timings
          if run.in_window(tm.finish) and tm.n_tokens > 1]
    return percentile(xs, 90) if xs else None


def setup_s(run) -> float:
    """Process start to the first request of the window."""
    return run.setup_s


# -- scheduler (program counters and telemetry trace) --------------------------
def queue_wait_p50(run) -> Optional[float]:
    """p50 of arrive -> admit over requests that arrived in the window
    (``Scheduler`` telemetry events); one still queued at the close counts
    with the time it has waited so far."""
    arrive, admit = {}, {}
    for ev in run.events:
        if ev.get("type") != "req":
            continue
        if ev["ev"] == "arrive":
            arrive[ev["rid"]] = ev["ts"]
        elif ev["ev"] == "admit" and ev["rid"] not in admit:
            admit[ev["rid"]] = ev["ts"]
    xs = [min(admit.get(rid, run.t_end), run.t_end) - t
          for rid, t in arrive.items() if run.in_window(t)]
    return percentile(xs, 50) if xs else None


def decode_occupancy(run) -> Optional[float]:
    """Mean of the ``slots.decoding`` gauge / slots, in %, over the ticks
    of the window that dispatched a decode horizon."""
    xs = [t["decoding"] for t in run.ticks if t["decode"]]
    return 100.0 * sum(xs) / len(xs) / run.max_seqs if xs else None


def prefix_hit_share(run) -> Optional[float]:
    """Prompt tokens served from the prefix cache / all prompt tokens
    admitted, in %, from the window's deltas of ``Scheduler.stats``."""
    reused = run.sched_stats.get("prefix_tokens_reused", 0)
    total = reused + run.sched_stats.get("prefill_tokens", 0)
    return 100.0 * reused / total if total else None


# -- engine and kernels (device trace) -----------------------------------------
def _program_s(run, kind: str) -> Optional[float]:
    if run.device is None:
        return None
    s = run.device["program_s"].get(kind, 0.0)
    return s if s > 0 else None


def prefill_ms_per_token(run) -> Optional[float]:
    """Device time of the prefill programs / prompt tokens prefilled."""
    s = _program_s(run, "prefill")
    n = run.sched_stats.get("prefill_tokens", 0)
    return 1e3 * s / n if s and n else None


def decode_step_ms(run) -> Optional[float]:
    """Device time of the decode programs / fused decode steps run."""
    s = _program_s(run, "decode")
    n = run.engine_stats.get("decode_steps", 0)
    return 1e3 * s / n if s and n else None


def decode_work(run):
    """Least seconds of the window's decode steps, step by step, and how
    many were bound by memory rather than compute."""
    hp, least, mem = run.cell.hp, 0.0, 0
    for ctxs in run.decode_steps:
        if not ctxs:
            continue
        w = counts.step_work(hp, ctxs)
        t = counts.least_seconds(w["flops"], w["bytes"], run.peak, run.chips)
        least += t["seconds"]
        mem += t["bound"] == "memory"
    return least, mem


def decode_roofline(run) -> Optional[float]:
    """Least time of the decode work / device time of the decode
    programs, in %."""
    s = _program_s(run, "decode")
    least, _ = decode_work(run)
    return 100.0 * least / s if s and least else None


def mfu(run) -> Optional[float]:
    """Model operations of every token computed in the traced window /
    (window x chips x bf16 peak), in %."""
    if run.device is None:
        return None
    hp = run.cell.hp
    flops = sum(counts.flops_per_token(hp, c) for c in run.prefill_ctx)
    flops += sum(counts.flops_per_token(hp, c)
                 for ctxs in run.decode_steps for c in ctxs)
    den = run.device["window_s"] * run.chips * run.peak["bf16_flops"]
    return 100.0 * flops / den if flops else None


def idle_share(run) -> Optional[float]:
    """1 - busy / window of the profiler trace, mean over chips, in %."""
    if run.device is None:
        return None
    return 100.0 * run.device["idle_share"]
