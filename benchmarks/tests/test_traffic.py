"""The seed decides order and phase, never the totals."""

import glob
import json
import os

import pytest

from lib import traffic

from conftest import BENCH, TESTS

FILES = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json"))
               + glob.glob(os.path.join(TESTS, "data", "traffic", "*.json")))
SEEDS = [0, 1, 2, 3, 17, 1234, 99991, 2**31 - 1, 2**31 + 5, 3000000017]


def offered(t: dict, seed: int, seconds: float = 40.0):
    if t["loop"] == "closed":
        return [r for c in traffic.closed_loop(t, seed) for r in c]
    return traffic.open_loop(t, seed, seconds)


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_totals_do_not_depend_on_the_seed(path):
    t = json.load(open(path))
    seen = {traffic.totals(offered(t, s)) for s in SEEDS}
    assert len(seen) == 1, seen
    n, prompt_tokens, output_tokens = seen.pop()
    assert n > 0 and prompt_tokens > 0 and output_tokens > 0
    orders = {tuple((r.prompt_tokens, r.max_new) for r in offered(t, s))
              for s in SEEDS}
    assert len(orders) == len(SEEDS), "seeds must give different orders"
    assert offered(t, 7) == offered(t, 7), "one seed, one schedule"


@pytest.mark.parametrize("path", [f for f in FILES if "open" in f],
                         ids=os.path.basename)
def test_open_arrivals_one_per_slot(path):
    t = json.load(open(path))
    assert t["loop"] == "open"
    for seed in SEEDS[:4]:
        reqs = [r for r in traffic.open_loop(t, seed, 40.0) if not r.warm]
        slot = 1.0 / t["rate_per_s"]
        w0 = t["warm_s"]
        assert len(reqs) == round(t["rate_per_s"] * 40.0)
        for i, r in enumerate(reqs):
            assert w0 + i * slot <= r.due_s < w0 + (i + 1) * slot


def test_every_hand_covers_the_distribution():
    import random

    grid = traffic.stratified_grid({"min": 16, "max": 192}, 64)
    hands = traffic.stratified_deal(grid, 8, random.Random(3))
    assert sorted(v for h in hands for v in h) == grid
    for hand in hands:  # one value from each of the 8 bands
        assert sorted(grid.index(v) // 8 for v in hand) == list(range(8))


@pytest.mark.parametrize("path", [f for f in FILES if "closed" in f],
                         ids=os.path.basename)
def test_steady_state_start_leaves_no_two_clients_finishing_together(path):
    """On an ideal server (one token per client per tick) a client's n-th
    request ends at the running sum of its output lengths. Without the cut
    opener and with one output length, all clients end together, wave after
    wave; with it, no tick may see more than a few of them."""
    t = json.load(open(path))
    for seed in SEEDS[:5]:
        ends: dict[int, int] = {}
        for client in traffic.closed_loop(t, seed):
            tick = 0
            for r in client[:5]:
                tick += r.max_new
                ends[tick] = ends.get(tick, 0) + 1
        assert max(ends.values()) <= max(3, t["clients"] // 10), ends
        # and the openers alone spread over their whole range
        openers = sorted(c[0].max_new for c in traffic.closed_loop(t, seed))
        assert len(set(openers)) >= 0.5 * t["clients"]


def test_prompt_text_has_the_stated_length():
    from symmetry_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer(32768)
    for n in (32, 33, 100, 416):
        r = traffic.Request(n, 8, seed=n * 7919)
        text = traffic.prompt_text(r, 19)
        ids = tok.apply_chat_template([{"role": "user", "content": text}])
        assert len(ids) == n
    a = traffic.prompt_text(traffic.Request(64, 8, 1), 19)
    b = traffic.prompt_text(traffic.Request(64, 8, 2), 19)
    assert a[:8] != b[:8], "no two prompts share a prefix"


def test_a_mix_that_cannot_fit_is_refused():
    t = {"prompt_tokens": {"min": 32, "max": 448},
         "output_tokens": {"min": 16, "max": 192}}
    with pytest.raises(traffic.TrafficError):
        traffic.check_fits(t, 608)
    traffic.check_fits(t, 640)
