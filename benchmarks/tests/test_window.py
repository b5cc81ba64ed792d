"""Window arithmetic on a synthetic event list."""

import math

from lib import window


def rec(due, stamps, t_done, tokens, **kw):
    return {"due": due, "t_send": kw.pop("t_send", due), "stamps": stamps,
            "t_done": t_done, "tokens": tokens, "finish": "length",
            "error": None, "prompt_tokens": 10, "max_new": tokens or 0,
            "warm": False, **kw}


RECORDS = [
    # in flight at the window's start: 3 chunks of 4 chars, 12 tokens
    rec(8.0, [[9.0, 4], [10.5, 4], [12.0, 4]], 12.0, 12),
    # wholly inside
    rec(11.0, [[11.5, 2], [13.5, 6]], 13.5, 16),
    # due inside, first token after the window, ends after it
    rec(19.0, [[21.0, 5], [22.0, 5]], 22.0, 10),
    # due inside, failed
    rec(15.0, [], 15.1, None, error="ProviderBusyError: shed"),
    # due inside, never got a first token
    rec(16.0, [], None, None),
]
W0, W1 = 10.0, 20.0


def test_tokens_in_window_by_arrival_stamp():
    # r0: 8 of 12 chars inside → 8 tokens; r1: all 16; r2: none
    assert window.window_tokens(RECORDS, W0, W1) == 8 + 16


def test_gaps_ending_in_the_window():
    gaps = sorted(window.window_gaps(RECORDS, W0, W1))
    # r0: 9→10.5 (ends inside), 10.5→12; r1: 11.5→13.5; r2's gap ends at 22
    assert gaps == [1.5, 1.5, 2.0]


def test_ttft_from_due_time_and_missing_counts_as_failed():
    ttfts, missing = window.window_ttfts(RECORDS, W0, W1)
    assert sorted(ttfts) == [0.5, 2.0]       # r1, r2 (due 19, first 21)
    assert missing == 2
    due = window.due_in_window(RECORDS, W0, W1)
    assert len(due) == 4 and sum(window.failed(r) for r in due) == 2


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert window.percentile(xs, 50) == 50
    assert window.percentile(xs, 99) == 99
    assert window.percentile([3.0], 99) == 3.0
    assert window.percentile([], 50) is None


def test_band_mean_is_the_ranks_between_two_percentiles():
    xs = list(range(1, 101))
    # nearest rank: the 90th percentile is 90, the 99th 99, both included
    assert window.band_mean(xs, 90, 99) == sum(range(90, 100)) / 10
    assert window.band_mean(xs, 80, 99) == sum(range(80, 100)) / 20
    assert window.band_mean([3.0], 90, 99) == 3.0
    assert window.band_mean([], 90, 99) is None
    # order does not matter, and a band of one rank is that percentile
    assert window.band_mean(xs[::-1], 99, 99) == window.percentile(xs, 99)


def test_tpot_and_live():
    assert window.tpots(RECORDS, W0, W1) == [3.0 / 11, 2.0 / 15]
    streams, tokens = window.live_at(RECORDS, 11.75)
    assert streams == 2
    # r0 has seen 8/12 chars of 12 tokens, r1 2/8 chars of 16 tokens
    assert math.isclose(tokens, (10 + 8) + (10 + 4))


def test_hist_delta_mean():
    start = {"count": 10, "mean": 1.0}
    end = {"count": 30, "mean": 2.0}
    assert math.isclose(window.hist_delta_mean(start, end), 2.5)
    assert window.hist_delta_mean(end, end) is None
    assert math.isclose(window.hist_delta_mean(None, end), 2.0)
