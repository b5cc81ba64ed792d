"""Traffic generation: one general generator that every traffic file drives.

A traffic file (`benchmarks/traffic/<mix>.json`) holds parameters only. This
module turns them and `--seed` into the requests a run offers. The rule that
shapes every function here: **the seed decides order and phase, never the
totals**. Lengths are a fixed stratified grid over the stated distribution,
permuted by the seed; open-loop arrivals are one per equal slot of the
schedule, jittered inside the slot by the seed. Every seed therefore offers
the same number of requests and the same prompt-token and output-token totals
in the measured window (`tests/test_traffic.py` pins it for ten seeds).

Nothing here imports the program under test.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass

# The driver's seeds pass 2**31; Python's Random takes any int, and the
# per-request sampling seeds handed to the program are folded to 31 bits.
_SEED_MASK = (1 << 31) - 1


class TrafficError(ValueError):
    """A traffic file that cannot be served by the configuration."""


@dataclass(frozen=True)
class Request:
    """One request as the generator offers it. `due_s` is the offset from the
    start of traffic (the warm phase starts at 0); closed loops leave it
    None — a closed client's request is due when the previous one ended."""

    prompt_tokens: int
    max_new: int
    seed: int
    due_s: float | None = None
    warm: bool = False


def stratified_grid(spec: dict, n: int) -> list[int]:
    """`n` lengths at the mid-quantiles of the stated distribution, ascending.

    spec: {"dist": "loguniform" | "uniform", "min": a, "max": b}. A grid is a
    deterministic function of (spec, n): no seed enters it."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 0 < lo <= hi:
        raise TrafficError(f"bad length range {spec}")
    dist = spec.get("dist", "loguniform")
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if dist == "loguniform":
            v = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
        elif dist == "uniform":
            v = lo + q * (hi - lo)
        else:
            raise TrafficError(f"unknown dist {dist!r}")
        out.append(max(lo, min(hi, int(round(v)))))
    return out


def stratified_deal(grid: list[int], groups: int,
                    rnd: random.Random) -> list[list[int]]:
    """Deal an ascending grid of `groups * per` values into `groups` hands of
    `per` values so that every hand holds one value from each of `per`
    quantile bands. Which value of a band a hand gets, and the order inside a
    hand, come from `rnd`; the multiset dealt is the grid itself, always.

    This is what keeps the tail's luck out of a window: every closed-loop
    client (a hand) cycles through the whole distribution, and every block of
    consecutive open-loop arrivals (a hand) covers it too."""
    if groups <= 0 or len(grid) % groups:
        raise TrafficError(f"grid of {len(grid)} does not deal into "
                           f"{groups} hands")
    per = len(grid) // groups
    hands: list[list[int]] = [[] for _ in range(groups)]
    for band in range(per):
        values = grid[band * groups:(band + 1) * groups]
        rnd.shuffle(values)
        for hand, v in zip(hands, values):
            hand.append(v)
    for hand in hands:
        rnd.shuffle(hand)
    return hands


def _lengths(spec_prompt: dict, spec_output: dict, groups: int, per: int,
             rnd: random.Random) -> list[list[tuple[int, int]]]:
    """`groups` hands of `per` (prompt, output) pairs; prompts and outputs
    are dealt independently, so any pairing can occur."""
    n = groups * per
    prompts = stratified_deal(stratified_grid(spec_prompt, n), groups, rnd)
    outputs = stratified_deal(stratified_grid(spec_output, n), groups, rnd)
    return [list(zip(p, o)) for p, o in zip(prompts, outputs)]


def check_fits(traffic: dict, max_total_tokens: int) -> None:
    """Every pairing of the two grids must fit a slot, so that no request is
    cut short by the cache's capacity (an operation that fails is not traffic
    a benchmark may offer)."""
    worst = int(traffic["prompt_tokens"]["max"]) + int(
        traffic["output_tokens"]["max"])
    if worst > max_total_tokens:
        raise TrafficError(
            f"prompt max + output max = {worst} tokens exceeds the "
            f"{max_total_tokens} a slot of this configuration can hold "
            f"with its decode lookahead")


def closed_loop(traffic: dict, seed: int) -> list[list[Request]]:
    """Per client, the list of requests it cycles through (in order, again
    from the start when exhausted).

    Steady-state start: each client's FIRST request is cut to a seed-drawn
    fraction of its output length (stratified over the clients, so the
    fractions are always the same set), which opens the loop already mixed
    instead of on a wave front of `clients` simultaneous admissions that
    finish together."""
    clients = int(traffic["clients"])
    per = int(traffic["requests_per_client"])
    rnd = random.Random(seed)
    hands = _lengths(traffic["prompt_tokens"], traffic["output_tokens"],
                     clients, per, rnd)
    fractions = [(i + 0.5) / clients for i in range(clients)]
    rnd.shuffle(fractions)
    out: list[list[Request]] = []
    for c, hand in enumerate(hands):
        reqs = [Request(p, o, rnd.randrange(_SEED_MASK)) for p, o in hand]
        first = reqs[0]
        cut = max(1, int(round(first.max_new * fractions[c])))
        # The cut request is extra: the client's cycle proper follows it, so
        # the multiset each client offers after its start is seed-free.
        out.append([Request(first.prompt_tokens, cut,
                            rnd.randrange(_SEED_MASK), warm=True)] + reqs)
    return out


def open_loop(traffic: dict, seed: int, seconds: float,
              rate: float | None = None) -> list[Request]:
    """The arrival schedule: a warm phase of `warm_s` then the window of
    `seconds`, each with its own grids, so the window's totals never depend
    on what the warm phase drew. One arrival per slot of 1/rate seconds,
    placed inside its slot by the seed."""
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    block = int(traffic.get("block", 16))
    rnd = random.Random(seed)
    out: list[Request] = []
    t0 = 0.0
    for phase_s, warm in ((float(traffic["warm_s"]), True),
                          (float(seconds), False)):
        n = int(round(rate * phase_s))
        if n <= 0:
            t0 += phase_s
            continue
        # Hands of about `block` consecutive arrivals, each covering the
        # distribution; the largest divisor of n keeps the grid exactly n,
        # so the totals are seed-free for every n.
        groups = max(g for g in range(1, max(1, n // block) + 1)
                     if n % g == 0)
        hands = _lengths(traffic["prompt_tokens"], traffic["output_tokens"],
                         groups, n // groups, rnd)
        pairs = [pair for hand in hands for pair in hand]
        slot = phase_s / n
        for i, (p, o) in enumerate(pairs):
            out.append(Request(p, o, rnd.randrange(_SEED_MASK),
                               due_s=t0 + (i + rnd.random()) * slot,
                               warm=warm))
        t0 += phase_s
    return out


def totals(requests: list[Request]) -> tuple[int, int, int]:
    """(count, prompt tokens, output tokens) of the measured requests."""
    measured = [r for r in requests if not r.warm]
    return (len(measured), sum(r.prompt_tokens for r in measured),
            sum(r.max_new for r in measured))


def prompt_text(request: Request, template_tokens: int) -> str:
    """ASCII filler whose byte-tokenised, chat-templated length is exactly
    `request.prompt_tokens`; distinct per request (its seed leads), so no two
    prompts share a prefix."""
    chars = request.prompt_tokens - template_tokens
    if chars < 8:
        raise TrafficError(
            f"a prompt of {request.prompt_tokens} tokens leaves {chars} "
            f"characters after the {template_tokens}-token chat template")
    head = f"{request.seed:08x} "
    words = "the quick brown fox jumps over the lazy dog and asks again "
    body = (words * (chars // len(words) + 1))[:chars - len(head)]
    return head + body
