"""The traffic generator and the end-to-end arithmetic, on the CPU."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import e2e, traffic  # noqa: E402

OPEN = {"loop": "open", "rate_per_s": 3.0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                   "min": 32, "max": 1536},
        "output": {"dist": "lognormal", "median": 96, "sigma": 1.0,
                   "min": 16, "max": 512},
        "slots": 32, "max_seq": 2048, "pool_bytes": 6e9}
CLOSED = {"loop": "closed", "clients": 8, "requests": 40,
          "prompt": {"dist": "uniform", "min": 3584, "max": 4096},
          "output": {"dist": "uniform", "min": 512, "max": 1024},
          "slots": 8, "max_seq": 5120, "pool_bytes": 6e9}


def _key(plan):
    return [(p.prompt.tolist(), p.max_new, p.due_s) for p in plan]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_schedule(mix):
    a = traffic.schedule(mix, 2 ** 33 + 17, 151936, 45.0)
    b = traffic.schedule(mix, 2 ** 33 + 17, 151936, 45.0)
    c = traffic.schedule(mix, 2 ** 33 + 18, 151936, 45.0)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_lengths_clipped_and_shared_across_seeds(mix):
    sets = []
    for seed in (1, 2, 3):
        plan = traffic.schedule(mix, seed, 1000, 45.0)
        lens = np.array([len(p.prompt) for p in plan])
        outs = np.array([p.max_new for p in plan])
        assert lens.min() >= mix["prompt"]["min"]
        assert lens.max() <= mix["prompt"]["max"]
        assert outs.min() >= mix["output"]["min"]
        assert outs.max() <= mix["output"]["max"]
        assert all(0 <= t < 1000 for p in plan for t in p.prompt)
        sets.append(sorted(zip(lens.tolist(), outs.tolist())))
    # every seed offers the same requests, in its own order
    assert sets[0] == sets[1] == sets[2]


@pytest.mark.parametrize("n", [1, 5, 64, 256])
def test_stratified_order_spreads_every_prefix(n):
    """A permutation whose first 2**k positions fall one into each of
    2**k equal blocks of indices."""
    for seed in (0, 2 ** 33 + 1):
        order = traffic.stratified_order(n, np.random.default_rng(seed))
        assert sorted(order.tolist()) == list(range(n))
        if n & (n - 1) == 0:
            for k in range(n.bit_length()):
                blocks = order[:2 ** k] // (n >> k)
                assert sorted(blocks.tolist()) == list(range(2 ** k))


def test_first_clients_get_the_same_work_under_every_seed():
    """The first block of a closed loop sets most of a window's work:
    its lengths span the whole distribution under every seed, and the
    requests that outlive a window keep nearly the same prompts."""
    mix = dict(CLOSED, requests=256, clients=64,
               prompt={"dist": "lognormal", "median": 128, "sigma": 1.0,
                       "min": 32, "max": 512},
               output={"dist": "lognormal", "median": 512, "sigma": 1.0,
                       "min": 128, "max": 2048})
    sums = []
    for seed in (1, 2, 3, 2 ** 33 + 5):
        plan = traffic.schedule(mix, seed, 1000, 50.0)[:64]
        sums.append((sum(p.max_new for p in plan),
                     sum(len(p.prompt) for p in plan),
                     sum(len(p.prompt) for p in plan if p.max_new > 470)))
    outs, prompts, survivors = np.array(sums).T
    assert outs.max() / outs.min() < 1.01
    assert prompts.max() / prompts.min() < 1.05
    assert survivors.max() / survivors.min() < 1.1


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    plan = traffic.schedule(OPEN, 5, 1000, 40.0)
    due = np.array([p.due_s for p in plan])
    assert len(plan) == 120                       # 3 req/s x 40 s
    assert np.all(np.diff(due) > 0)
    assert 0 < due[0] and due[-1] < 40.0
    gaps_a = sorted(np.diff(due))
    gaps_b = sorted(np.diff([p.due_s for p in
                             traffic.schedule(OPEN, 6, 1000, 40.0)]))
    assert len(gaps_a) == len(gaps_b)


def test_lognormal_median_holds():
    lens = traffic._quantiles({"dist": "lognormal", "median": 128,
                               "sigma": 1.0, "min": 1, "max": 10 ** 6}, 1001)
    assert lens[500] == 128


def test_check_sample_keeps_the_longest():
    served = {0: 5, 1: 40, 2: 7, 3: 9, 4: 1}
    pick = traffic.check_sample(list(served), served, 9, 3)
    assert pick[0] == 1 and len(pick) == 3 and len(set(pick)) == 3
    assert traffic.check_sample([], {}, 9, 3) == []


def test_pool_pages_fits_the_budget():
    mix = {"slots": 32, "max_seq": 2048, "pool_bytes": 6.04e9}
    assert traffic.pool_pages(mix, 147456, 16) == 2560
    assert traffic.pool_pages(mix, 36864, 16) == 32 * 128


def _rec(due, stamps, prompt_len=10):
    return e2e.Record(due=due, prompt_len=prompt_len, stamps=list(stamps))


def test_ttft_censored_at_window_end():
    recs = {0: _rec(1.0, [1.5, 1.6]),
            1: _rec(2.0, []),              # still waiting at t1 = 10
            2: _rec(9.0, [11.0]),          # first token after the window
            3: _rec(12.0, [12.5])}         # due after the window: not counted
    t = sorted(e2e.ttfts(recs, 0.0, 10.0))
    assert t == pytest.approx([0.5, 1.0, 8.0])


def test_tbt_takes_every_gap_in_the_window():
    recs = {0: _rec(0.0, [1.0, 1.1, 1.2, 5.0]),
            1: _rec(0.0, [-1.0, 2.0, 2.5, 11.0])}
    g = sorted(e2e.gaps(recs, 0.0, 10.0))
    assert g == pytest.approx([0.1, 0.1, 0.5, 3.8])


def test_a_stall_moves_all_three():
    """A 1 s stall inside the window raises the TTFT tail and the TBT tail
    and lowers the output rate, since none of them leaves it out: every
    request emits a token per 0.5 s tick until the window closes."""
    def window(stall):
        recs = {i: _rec(0.0 if i < 5 else 3.9, []) for i in range(10)}
        t = 0.0
        while True:
            t += 0.5
            if stall and 4.0 <= t < 4.5:
                t += 1.0
            if t > 6.0:
                break
            for r in recs.values():
                if r.due < t:
                    r.stamps.append(t)
        names = ["ttft_p90_ms", "tbt_p50_ms", "tbt_p95_ms", "output_tok_s"]
        return e2e.metrics(recs, 0.0, 6.0, names)

    calm, stalled = window(False), window(True)
    assert stalled["tbt_p95_ms"] > calm["tbt_p95_ms"]
    assert stalled["ttft_p90_ms"] > calm["ttft_p90_ms"]
    assert stalled["output_tok_s"] < calm["output_tok_s"]


def test_rate_is_over_the_whole_window():
    recs = {0: _rec(0.0, [0.1, 0.2, 0.3, 0.4])}
    assert e2e.metrics(recs, 0.0, 4.0, ["output_tok_s"])["output_tok_s"] \
        == pytest.approx(1.0)
