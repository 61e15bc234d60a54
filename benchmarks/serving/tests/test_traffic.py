"""The traffic generator: determinism, independence of the rate, session
structure, clips, and discovery of new mixes and metrics by name."""
from __future__ import annotations

import json

import numpy as np

import harness
from traffic.generator import (HERE, _quantile, closed_streams, draws,
                               load_mix, open_schedule, per_client,
                               percentile, schedule_bytes)

SEED = 2 ** 31 + 977
VOCAB = 151936


def _flat(streams):
    return [r for s in streams for r in s]


def test_one_seed_gives_the_same_schedule_byte_for_byte():
    for name in ("sessions",):
        mix = load_mix(name)
        a = schedule_bytes(_flat(closed_streams(mix, VOCAB, SEED, 8)))
        b = schedule_bytes(_flat(closed_streams(mix, VOCAB, SEED, 8)))
        c = schedule_bytes(_flat(closed_streams(mix, VOCAB, SEED + 1, 8)))
        assert a == b and a != c
    mix = load_mix("short")
    a = schedule_bytes(open_schedule(mix, VOCAB, SEED, mix["rate"], 200))
    b = schedule_bytes(open_schedule(mix, VOCAB, SEED, mix["rate"], 200))
    assert a == b


def test_draws_do_not_depend_on_the_rate():
    mix = load_mix("short")
    slow = open_schedule(mix, VOCAB, SEED, 1.0, 300)
    fast = open_schedule(mix, VOCAB, SEED, 7.5, 300)
    for s, f in zip(slow, fast):
        assert np.array_equal(s.prompt, f.prompt)
        assert s.max_new == f.max_new
        assert np.isclose(s.arrival, 7.5 * f.arrival)


def _shape(reqs):
    return [(r.client, len(r.prompt), r.max_new, r.arrival, r.think,
             r.session, r.turn) for r in reqs]


def test_every_seed_offers_the_same_schedule_with_other_tokens():
    mix = load_mix("short")
    a = open_schedule(mix, VOCAB, 1, mix["rate"], 256)
    b = open_schedule(mix, VOCAB, 2, mix["rate"], 256)
    assert _shape(a) == _shape(b)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    for name in ("sessions",):
        mix = load_mix(name)
        a = _flat(closed_streams(mix, VOCAB, 1, 6))
        b = _flat(closed_streams(mix, VOCAB, 2 ** 40 + 1, 6))
        assert _shape(a) == _shape(b)
        assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_open_loop_blocks_tile_the_time_axis():
    mix = load_mix("short")
    block, rate = mix["block"], mix["rate"]
    reqs = open_schedule(mix, VOCAB, SEED, rate, 4 * block)
    span = block / rate
    for b in range(4):
        t = [r.arrival for r in reqs[b * block:(b + 1) * block]]
        assert np.isclose(t[0], b * span) and t[-1] < (b + 1) * span
    # every block offers the same sizes
    sizes = [sorted(len(r.prompt) for r in reqs[b * block:(b + 1) * block])
             for b in range(4)]
    assert all(x == sizes[0] for x in sizes)


def test_each_round_of_a_closed_loop_offers_the_same_sizes():
    mix = load_mix("sessions")
    for name in ("prompt", "output"):
        sizes = per_client(mix[name], 4, mix["clients"],
                           mix["schedule_seed"], name)
        rounds = [sorted(sizes[:, j]) for j in range(4)]
        assert all(r == rounds[0] for r in rounds)
        assert list(sizes[:, 0]) != list(sizes[:, 1])


def test_every_session_turn_extends_the_previous_turn():
    mix = load_mix("sessions")
    turns = 0
    for stream in closed_streams(mix, VOCAB, SEED, 24):
        for prev, cur in zip(stream, stream[1:]):
            if cur.session == prev.session:
                turns += 1
                assert cur.turn == prev.turn + 1
                n = len(prev.prompt)
                assert np.array_equal(cur.prompt[:n], prev.prompt)
                # the synthetic answer is exactly the previous max_new
                assert len(cur.prompt) - n - prev.max_new >= \
                    mix["prompt"]["min"]
            else:
                assert cur.turn == 0 or cur.session == 0
    assert turns > 0


def test_every_length_stays_inside_its_clip():
    mix = load_mix("sessions")
    for r in _flat(closed_streams(mix, VOCAB, SEED, 24)):
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert len(r.prompt) + r.max_new <= mix["max_context"]
    mix = load_mix("short")
    for r in open_schedule(mix, VOCAB, SEED, mix["rate"], 500):
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]


def _dists(mix):
    for key, d in mix.items():
        if isinstance(d, dict) and "dist" in d:
            yield key, d
        elif isinstance(d, dict):
            yield from _dists(d)


def test_each_mix_draws_the_mean_its_source_publishes():
    """A length or count with a ``source_mean`` is drawn, one block of the
    generator's quantiles clipped and rounded, at that mean to 2%."""
    found = 0
    for path in sorted(HERE.glob("*.json")):
        mix = load_mix(path.stem)
        assert mix.get("source"), f"{path.name} names no source"
        block = mix.get("block", mix.get("clients"))
        for key, d in _dists(mix):
            if "source_mean" not in d:
                continue
            u = (np.arange(block) + 0.5) / block
            q = np.clip(_quantile(d, u), d.get("min", -np.inf),
                        d.get("max", np.inf))
            mean = (np.rint(q) if d.get("int") else q).mean()
            assert abs(mean / d["source_mean"] - 1) < 0.02, (path.name, key)
            found += 1
    assert found >= 5


def test_stratified_draws_hold_the_distribution():
    rng = np.random.default_rng(0)
    x = draws({"dist": "lognormal", "median": 48, "sigma": 0.8}, 640, rng)
    assert abs(np.median(x) - 48) < 3
    z = draws({"dist": "zipf", "n": 4, "s": 1.0, "int": True}, 6400, rng)
    share = np.bincount(z, minlength=5)[1:] / len(z)
    assert np.allclose(share, np.array([12, 6, 4, 3]) / 25, atol=0.02)


def test_percentile_is_linear_interpolation():
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile([5.0], 90) == 5.0
    assert np.isclose(percentile(range(11), 90), 9.0)


def test_new_mix_and_metric_are_found_by_name(tmp_path):
    """A later PR adds a cell as files and entries only: a new mix under
    traffic/, a new reader under metrics/, a new entry in BENCHMARK.json."""
    root = tmp_path / "checkout"
    here = root / "benchmarks" / "serving"
    (here / "traffic").mkdir(parents=True)
    (here / "metrics").mkdir()
    (here / "configs").mkdir()
    (here / "limits").mkdir()
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (here / "configs" / "qwen3-0.6b.json").write_text(
        (harness.HERE / "configs" / "qwen3-0.6b.json").read_text())
    mix = dict(load_mix("short"), rate=2.5)
    (here / "traffic" / "newmix.json").write_text(json.dumps(mix))
    (here / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (here / "limits" / "new-cell.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 1.0}}))
    bench["workloads"].append({"name": "new-cell", "config": "qwen3-0.6b",
                               "traffic": "newmix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "ttft_p90",
                               "workloads": ["new-cell"]})
    bench["end_to_end"][1]["workloads"].append("new-cell")
    for c in bench["configs"]:
        c["file"] = "benchmarks/serving/configs/qwen3-0.6b.json"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("new-cell", root=root, here=here)
    assert cell.mix["rate"] == 2.5
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert "ttft_p90" in [m["name"] for m in cell.end_to_end]
    assert cell.limits == {"max_logit_gap": {"limit": 1.0}}
    assert harness.read_metric(cell.per_layer[0], None, here=here) == 42.0
