"""Percentiles, token gaps, the traffic generators and the peaks table:
everything that needs no JAX."""

import json
import os

import pytest

from perfbench import core, lengths, peaks, readers, stats
from perfbench.traffic_kinds import load_kind

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_gaps_and_rates_from_a_hand_made_step_log():
    class Req:
        status, output, max_new_tokens = "ok", [1, 2, 3], 3

    def tracked(due, stamps, standing=False):
        return core.Tracked(planned=None, req=Req(), due_t=due,
                            submit_t=due + 0.001, stamps=stamps,
                            done_t=stamps[-1], standing=standing)

    rec = core.Records(
        t_open=10.0, t_close=11.0, t_end=11.5,
        setup_s=2.0, tracked=[
            # in flight before the opening: no wait, and its first gap
            # (begun before the opening) is not a gap of the window
            tracked(9.0, [9.90, 10.05, 10.12], standing=True),    # 70 ms
            tracked(10.0, [10.10, 10.15, 10.25]),     # gaps 50 ms, 100 ms
            tracked(10.5, [10.90, 11.20, 11.30])],    # 300 ms gap ends outside
        steps=[(10.0, 10.1, "mixed", 0, 1, 0), (10.1, 10.15, "decode", 1, 1, 5),
               (10.15, 10.25, "decode", 2, 1, 6), (11.1, 11.2, "decode", 1, 1, 7)],
        kv_live=[0.25, 0.5], counters={"preemptions": 0.0}, queue_wait_s=[0.01],
        sizes=None, family=None, n_slots=2, n_chips=1, device_kind="cpu")
    assert readers.ttft_ms(rec) == pytest.approx([100.0, 400.0])
    assert sorted(readers.gaps_ms(rec)) == pytest.approx([50.0, 70.0, 100.0])
    from perfbench.end_to_end import ttft_mean_ms
    from perfbench.layer_metrics import ttft_p50_ms, ttft_p95_ms

    assert ttft_mean_ms.read(rec) == pytest.approx(250.0)
    assert ttft_p50_ms.read(rec) == pytest.approx(250.0)
    assert ttft_p95_ms.read(rec) == pytest.approx(400.0)
    from perfbench.end_to_end import out_tokens_per_s
    from perfbench.layer_metrics import (decode_occupancy, decode_step_ms,
                                         kv_used_share_peak)

    assert out_tokens_per_s.read(rec) == pytest.approx(6.0)   # 6 stamps inside
    assert decode_step_ms.read(rec) == pytest.approx(75.0)    # median 50, 100
    assert decode_occupancy.read(rec) == pytest.approx(75.0)  # 3 rows / 2x2
    assert kv_used_share_peak.read(rec) == pytest.approx(50.0)
    assert core.count_requests(rec) == (3, 0)
    rec.tracked[1].stamps = []                 # never got a first token
    rec.tracked[2].req = type("R", (), {"status": "failed", "output": [],
                                        "max_new_tokens": 3})()
    assert core.count_requests(rec) == (3, 2)


def load_mix(mix):
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        return json.load(f)


KW = dict(seconds=20.0, vocab=1000, max_total=4096, n_slots=4)


def whole(params, seed):
    plan = load_kind(params["kind"])(params, seed=seed, **KW)
    return plan.standing(), plan.take_due(1e9)


def test_open_loop_deals_the_same_sizes_and_gaps_in_another_order_by_seed():
    params = {**load_mix("chat"), "rate_rps": 3.0,
              "standing": {"token_s": 0.1, "prefill_tokens_per_s": 500}}
    (sa, a), (sb, b), (sc, c) = (whole(params, s)
                                 for s in (5, 5, 2 ** 31 + 7))

    def rows(ps):
        return [(p.due_s, p.prompt, p.max_new_tokens) for p in ps]

    assert rows(a) == rows(b) and rows(sa) == rows(sb)    # seeded
    assert len(a) == len(c) == 60 and a[-1].due_s < KW["seconds"]
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, c))  # the same sizes
        assert list(map(key, a)) != list(map(key, c))      # another order

    def gaps(ps):
        ts = [0.0] + [p.due_s for p in ps]
        return sorted(round(y - x, 9) for x, y in zip(ts, ts[1:]))

    assert gaps(a) == gaps(c)
    # a prompt, its answer and the gap AFTER it are one unit for every seed
    # (the longest gap's unit comes last, half of that gap before the first
    # arrival): what an arrival costs the next one is not the seed's to deal

    def units(ps):
        after = ([y.due_s - x.due_s for x, y in zip(ps, ps[1:])]
                 + [2 * ps[0].due_s])
        return sorted((len(p.prompt), p.max_new_tokens, round(g, 9))
                      for p, g in zip(ps, after))

    assert units(a) == units(c) and len(set(units(a))) == 60
    assert 2 * a[0].due_s == pytest.approx(max(u[2] for u in units(a)))
    # every eight arrivals in a row span the lengths: none holds only the
    # short or only the long half
    median = sorted(len(p.prompt) for p in a)[30]
    for i in range(0, 56, 8):
        longer = sum(len(p.prompt) > median for p in a[i:i + 8])
        assert 2 <= longer <= 6
    for p in a:
        assert params["prompt"]["lo"] <= len(p.prompt) <= params["prompt"]["hi"]
        assert params["output"]["lo"] <= p.max_new_tokens <= params["output"]["hi"]
        assert all(0 <= t < KW["vocab"] for t in p.prompt)
    # the standing population: requests of the same process caught part-way.
    # At 3 req/s and lives of about (600 / 500 + 200 x 0.1) s, some tens.
    assert 30 <= len(sa) <= 90 and 30 <= len(sc) <= 90
    assert all(p.standing and p.due_s == 0.0 and p.max_new_tokens >= 1
               and len(p.prompt) + p.max_new_tokens <= KW["max_total"]
               for p in sa)
    assert max(len(p.prompt) for p in sa) > params["prompt"]["hi"]
    # the same population for every seed, with the seed's own token ids

    def sizes(ps):
        return [(len(p.prompt), p.max_new_tokens) for p in ps]

    assert sizes(sa) == sizes(sc)
    assert [p.prompt for p in sa] != [p.prompt for p in sc]
    del params["standing"]
    assert whole(params, 5)[0] == []          # no pace stated: an idle fleet


def test_open_loop_pairing_takes_the_draw_of_collisions_from_the_seed():
    """In a plain model of the served path (one prompt at a time, a step
    for every 448 tokens, the next arrival waits for what is left) the sum
    of the waits that arrivals cost each other is the same for every seed
    up to the chains of three, where it followed the seed's draw before."""
    params = {**load_mix("chat"), "rate_rps": 0.8}
    step_s, rows = 0.023, 448

    def waits_cost(seed):
        plan = load_kind("open_poisson")(params, seed=seed, seconds=40.0,
                                         vocab=1000, max_total=4096,
                                         n_slots=32)
        busy_until, cost = 0.0, 0.0
        for p in plan.requests:
            start = max(p.due_s, busy_until)
            cost += start - p.due_s
            busy_until = start + step_s * -(-len(p.prompt) // rows)
        return cost

    costs = sorted(waits_cost(seed) for seed in range(40))
    # one prompt of 1,407 tokens with 20 ms behind it, in every seed; four
    # seeds in forty chain a third arrival onto it
    assert costs[0] == pytest.approx(0.072, abs=0.002)
    assert costs[33] - costs[0] < 1e-6 and costs[-1] < 0.1


def test_closed_loop_runs_the_same_schedule_for_every_seed():
    params = load_mix("reasoning")
    (sa, _), (sc, _) = whole(params, 5), whole(params, 2 ** 31 + 7)
    assert len(sa) == KW["n_slots"] and all(p.standing for p in sa)
    assert [p.prompt for p in sa] != [p.prompt for p in sc]
    assert [(len(p.prompt), p.max_new_tokens) for p in sa] == \
        [(len(p.prompt), p.max_new_tokens) for p in sc]
    # caught part-way: contexts longer than any prompt, answers cut short
    assert max(len(p.prompt) for p in sa) > params["prompt"]["hi"]
    assert len({p.max_new_tokens for p in sa}) == len(sa)    # out of step
    for p in sa:
        assert 1 <= p.max_new_tokens <= params["output"]["hi"]
        assert len(p.prompt) + p.max_new_tokens <= KW["max_total"]


def test_closed_loop_sends_the_next_request_when_one_finishes():
    params = {"kind": "closed_loop", "clients": "n_slots", "rounds": 2,
              "prompt": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 16},
              "output": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 16}}
    plan = load_kind("closed_loop")(params, seed=1, seconds=5.0, vocab=50,
                                    max_total=64, n_slots=3)
    first = plan.standing()
    assert len(first) == 3 and plan.take_due(1.0) == []
    assert len({p.max_new_tokens for p in first}) > 1     # out of step
    assert all(len(p.prompt) + p.max_new_tokens <= 16 + 16 for p in first)
    plan.on_finish(first[1], 2.0)
    nxt = plan.take_due(2.5)
    assert [p.client for p in nxt] == [first[1].client]
    assert nxt[0].due_s == 2.0
    plan.close()
    plan.on_finish(first[0], 3.0)
    assert plan.take_due(4.0) == [] and plan.next_due_s() is None


def test_fit_lengths():
    assert lengths.fit_lengths(3000, 768, 4096) == (3000, 768)
    assert lengths.fit_lengths(3900, 768, 4096) == (3328, 768)


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


def test_min_bytes_of_a_decode_step():
    from perfbench import families

    with open(os.path.join(BENCH, "configs", "qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    family = families.load_family(cfg)
    m = family.sizes(cfg)
    assert family.layer_matmul_params(m) == 1_409_286_144
    assert family.kv_bytes_per_token(m) == 112 * 1024
    b = family.decode_step_min_bytes(m, [1000] * 32)
    assert b == (1_409_286_144 + 2048 * 151_936) * 2 + 32_000 * 112 * 1024
