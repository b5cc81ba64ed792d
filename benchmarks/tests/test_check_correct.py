"""`run.py check_correct` on hand-made records: a sampled EOS as a stream's
FIRST token (0 tokens, `finish: stop`) is what one seed in some tens draws
once and is accepted; one stream in a hundred or more ending so is a program
that stopped answering, and is reported with its count."""

from types import SimpleNamespace as NS

import pytest

import conftest  # noqa: F401  (puts benchmarks/ on the path)
import run


def phase(n_full, n_empty, **odd):
    """`n_full` streams that delivered what they asked for, `n_empty` that
    ended `stop` before their first token, and one more record of `odd`."""
    full = {"tokens": 165, "max_new": 165, "finish": "length", "t_done": 1.0}
    empty = dict(full, tokens=0, finish="stop")
    records = [dict(full) for _ in range(n_full)] + [
        dict(empty) for _ in range(n_empty)]
    if odd:
        records.append(dict(full, **odd))
    wire = sum(r["tokens"] for r in records) + 8
    return NS(records=records, stats_end={
        "engine": {"tokens": wire}, "tokens_out": wire, "in_flight": 0})


@pytest.mark.parametrize("n_full,n_empty,odd,expect", [
    (999, 0, {}, None),
    # one first-token EOS among a thousand streams: correct
    (999, 1, {}, None),
    (298, 2, {}, None),
    # one in a hundred, and more: not correct, with the count
    (99, 1, {}, "1 of 100 completed streams ended `stop` with 0 tokens"),
    (960, 40, {}, "40 of 1000 completed streams ended `stop` with 0 tokens"),
    # every other condition is as strict as it was
    (999, 0, {"tokens": 0, "finish": "length"}, "delivered 0"),
    (999, 0, {"tokens": 170, "finish": "stop"}, "delivered 170"),
    (999, 0, {"tokens": 90, "finish": "stop"}, None),
    (999, 0, {"t_done": None}, "left open"),
])
def test_a_first_token_eos_is_accepted_while_rare(n_full, n_empty, odd,
                                                  expect):
    why = run.check_correct(phase(n_full, n_empty, **odd), ["a", "a"], 8)
    if expect is None:
        assert why == []
    else:
        assert len(why) == 1 and expect in why[0], why


def test_every_number_compared_has_a_name_and_a_limit():
    """`compare` is what the result line's `compared` key and the last
    lines on stderr print: each number beside its limit, JSON-clean."""
    import json

    numbers = run.compare(phase(99, 1), ["a", "b"], 8)
    table = {name: [value, limit] for name, value, limit, _ in numbers}
    assert json.loads(json.dumps(table, allow_nan=False)) == table
    assert table["empty_stop_streams"] == [1, 0]
    assert table["greedy_probe_mismatch"] == [1, 0]
    assert table["short_streams"] == table["open_streams"] == [0, 0]
    assert table["wire_host_token_gap"] == [0, 0]
    over = sorted(name for name, value, limit, _ in numbers if value > limit)
    assert over == ["empty_stop_streams", "greedy_probe_mismatch"]
    assert len(run.check_correct(phase(99, 1), ["a", "b"], 8)) == 2
