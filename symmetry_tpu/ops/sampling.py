"""Token sampling: greedy / temperature / top-k / top-p, batched and jittable.

Controls are per-slot arrays, not Python scalars, so one compiled sampler
serves a continuous batch where every request carries its own temperature
(InferenceRequest sampling fields, provider/backends/base.py). temperature==0
selects greedy via masking rather than control flow — no recompiles, no
data-dependent branching under jit.

Perf note: a full [B, V] sort at V=128k costs more than the decode matmuls
for small models, so sampling is restricted to the top `cap` logits. One
`lax.top_k` over the whole vocabulary is not cheap on a TPU either: its
time is proportional to V and to nothing else — 3.5 ms a decode step on
[128, 152064], four times the LM head that produced the logits, 0.75 ms
on [128, 32768] (PERF_LEDGER.jsonl PR 25; PERF.md §6, PR 26). So where the
vocabulary is wide enough (`top_k_route`: from 16,257 entries at cap 64) the
window is selected in two stages (`_top_k`): the maxima of groups of
TOP_K_GROUP_WIDTH consecutive entries choose the `cap` groups that can hold
the top `cap`, and only those are ranked — 0.75 ms a step at 152,064. Exact,
ties included: `lax.top_k` is stable (lowest index first), so an entry
left out sits behind `cap` groups whose maxima outrank it — `cap` entries
ahead of it; and the chosen groups are gathered in ascending order, so
candidate order is vocabulary order and ties break as in the single call.
Greedy and any top_k <= cap are exact; top-p loses only the probability
mass beyond the top `cap` tokens (< 1e-3 for typical LM distributions at
cap=64).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.attention import NEG_INF

SAMPLING_TOP_CAP = 64
# Width of a vocabulary group in the two-stage selection, and how many
# groups per kept entry make it worth taking: fixed once from a sweep of
# W in {128, 256, 512} at V = 152064 and 32768 on a v5e (PERF.md §6, PR 26).
TOP_K_GROUP_WIDTH = 128
TOP_K_MIN_GROUPS_PER_CAP = 2


def top_k_route(vocab: int, cap: int = SAMPLING_TOP_CAP) -> dict:
    """How `_top_k` selects the top `cap` of `vocab` logits — decided by
    the two static sizes alone, so every call of a served program takes
    the same route and the engine can report it (startup.sampling)."""
    cap = min(cap, vocab)
    groups = -(-vocab // TOP_K_GROUP_WIDTH)
    if groups < TOP_K_MIN_GROUPS_PER_CAP * cap:
        return {"top_k": "direct"}
    return {"top_k": "grouped", "groups": groups,
            "width": TOP_K_GROUP_WIDTH, "cap": cap}


def _grouped_top_k(x: jnp.ndarray, cap: int,
                   width: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`lax.top_k(x, cap)` over the last axis, values and indices equal,
    ranking groups + cap*width entries instead of all of them (module
    docstring has why it is exact). Needs at least `cap` groups."""
    *lead, vocab = x.shape
    groups = -(-vocab // width)
    pad = groups * width - vocab
    if pad:  # -inf at the highest indices: behind every real entry
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, pad)],
                    constant_values=-jnp.inf)
    grouped = x.reshape(*lead, groups, width)
    _, chosen = jax.lax.top_k(grouped.max(-1), cap)
    chosen = jnp.sort(chosen, axis=-1)  # candidates in vocabulary order
    candidates = jnp.take_along_axis(grouped, chosen[..., None], axis=-2)
    values, pos = jax.lax.top_k(candidates.reshape(*lead, cap * width), cap)
    group = jnp.take_along_axis(chosen, pos // width, axis=-1)
    return values, group * width + pos % width


def _top_k(x: jnp.ndarray, cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The top `cap` of the last axis, descending, with their indices:
    `lax.top_k`'s result by the route `top_k_route` names."""
    route = top_k_route(x.shape[-1], cap)
    if route["top_k"] == "direct":
        return jax.lax.top_k(x, cap)
    return _grouped_top_k(x, cap, route["width"])


def _masked_top_logits(
    logits: jnp.ndarray,        # [..., V] float
    temperature: jnp.ndarray,   # [B] float; 0 => greedy
    top_p: jnp.ndarray,         # [B] float in (0, 1]; 1 => disabled
    top_k: jnp.ndarray,         # [B] int32; 0 => disabled
    cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The shared sampling-distribution core: temperature-scaled logits
    restricted to the top-`cap` window with the greedy/top-k/top-p keep
    mask applied (NEG_INF elsewhere). Returns (masked [..., cap], vocab
    indices [..., cap]). Factored out of sample_tokens so the speculative
    verify pass (verify_tokens) scores drafts against EXACTLY the
    distribution the decode path samples from — the acceptance rule is
    only unbiased if the two share one definition of the target."""
    extra = logits.ndim - 2  # broadcast per-slot controls over mid axes
    ctl = (slice(None),) + (None,) * extra

    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[ctl + (None,)]

    # Partial sort: [..., cap] descending, with original vocab indices.
    top_logits, top_idx = _top_k(scaled, cap)

    ranks = jnp.arange(cap, dtype=jnp.int32)
    # top-k: keep ranks < k (0 disables; anything beyond cap acts as cap).
    # Greedy (temperature == 0) is expressed as k = 1: with only rank 0
    # unmasked, a categorical draw deterministically returns the argmax —
    # one select lane, no separate greedy branch.
    k = jnp.where(top_k > 0, top_k, cap)
    k = jnp.where(temperature > 0, k, 1)
    keep = ranks < k[ctl + (None,)]
    # top-p: keep the smallest prefix whose probability mass reaches p.
    # (Mass is computed over the top-cap window — the tail beyond cap is
    # treated as zero, see module docstring.)
    probs = jax.nn.softmax(top_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # token i is kept if the mass strictly before it is < p (always keeps rank 0)
    mass_before = cum - probs
    keep &= mass_before < top_p[ctl + (None,)]

    return jnp.where(keep, top_logits, NEG_INF), top_idx


def sample_tokens(
    logits: jnp.ndarray,        # [B, V] float
    key: jax.Array,             # PRNG key — scalar, or [B] per-slot keys
    temperature: jnp.ndarray,   # [B] float; 0 => greedy
    top_p: jnp.ndarray,         # [B] float in (0, 1]; 1 => disabled
    top_k: jnp.ndarray,         # [B] int32; 0 => disabled
    cap: int = SAMPLING_TOP_CAP,
) -> jnp.ndarray:
    """Returns sampled token ids [B] int32."""
    B, V = logits.shape
    cap = min(cap, V)
    logits = logits.astype(jnp.float32)

    masked, top_idx = _masked_top_logits(logits, temperature, top_p, top_k,
                                         cap)
    if key.ndim:  # [B] per-slot keys: each row draws from its own stream
        choice_rank = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(key, masked)
    else:
        choice_rank = jax.random.categorical(key, masked, axis=-1)  # [B]
    sampled = jnp.take_along_axis(top_idx, choice_rank[:, None], axis=-1)[:, 0]
    return sampled.astype(jnp.int32)


def verify_tokens(
    logits: jnp.ndarray,        # [B, S, V] float; S = 1 + k draft lanes
    draft: jnp.ndarray,         # [B, k] int32 proposed tokens
    n_draft: jnp.ndarray,       # [B] int32 valid proposals per slot (0..k)
    key: jax.Array,             # [B] per-slot PRNG keys
    temperature: jnp.ndarray,   # [B] float; 0 => greedy
    top_p: jnp.ndarray,         # [B] float in (0, 1]
    top_k: jnp.ndarray,         # [B] int32
    cap: int = SAMPLING_TOP_CAP,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Speculative-decoding acceptance (Leviathan et al.; PAPERS.md) over
    one batched verify forward. `logits[:, j]` is the target model's
    next-token distribution given the context plus draft[:, :j] — the
    verify pass fed [last_token, draft...] so position j scores proposal
    draft[:, j] and position n_draft holds the all-accepted bonus.

    Acceptance per slot: draft tokens are accepted left to right while
    u_j < p_target(draft_j) with u_j ~ U[0,1) — the n-gram drafter is a
    DETERMINISTIC proposer (q = point mass), for which this rule is the
    standard rejection test. On the first rejection the bonus token is
    drawn from the residual distribution (the target with the rejected
    proposal removed, renormalized); with every proposal accepted it is
    drawn from the target at the next position. Net effect: every emitted
    token is distributed EXACTLY as sequential sampling from the same
    masked distribution — greedy lanes (temperature 0 => a one-hot keep
    set) accept iff the draft equals the argmax, making speculative
    greedy output token-identical to plain decode.

    Returns (out [B, S], n_emit [B]): out[b, :n_emit[b]] are the tokens
    to emit this dispatch — n_emit-1 accepted drafts plus the bonus —
    and n_emit is always >= 1, so a slot with no proposals advances
    exactly like a plain decode step.
    """
    B, S, V = logits.shape
    cap = min(cap, V)
    logits = logits.astype(jnp.float32)

    masked, top_idx = _masked_top_logits(logits, temperature, top_p, top_k,
                                         cap)  # [B, S, cap] x2
    p = jax.nn.softmax(masked, axis=-1)  # target probs over the keep set

    # Probability the target assigns to each proposal (0 when the proposal
    # is outside the top-cap keep window). Lane S-1 has no proposal — pad
    # with zeros; the validity mask below keeps it out of the accept scan.
    draft_ext = jnp.concatenate(
        [draft, jnp.zeros((B, 1), draft.dtype)], axis=1)      # [B, S]
    match = top_idx == draft_ext[:, :, None]                  # [B, S, cap]
    p_draft = jnp.sum(jnp.where(match, p, 0.0), axis=-1)      # [B, S]

    ks = jax.vmap(lambda q: jax.random.split(q, 3))(key)      # [B, 3]
    u = jax.vmap(lambda q: jax.random.uniform(q, (S,)))(ks[:, 0])
    lane = jnp.arange(S, dtype=jnp.int32)[None, :]
    accept = (u < p_draft) & (lane < n_draft[:, None])        # [B, S]
    # Longest accepted prefix: rejections (and the padded tail) stop it.
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

    # Bonus-token candidates at every position, selected by n_acc below:
    #  - residual: the target with the rejected proposal removed (softmax
    #    over the remaining keep set renormalizes), for a mid-run stop;
    #  - full: a plain target draw, for the all-proposals-accepted lane.
    resid = jnp.where(match, NEG_INF, masked)
    r_rank = jax.vmap(lambda q, row: jax.random.categorical(q, row))(
        ks[:, 1], resid)                                      # [B, S]
    f_rank = jax.vmap(lambda q, row: jax.random.categorical(q, row))(
        ks[:, 2], masked)
    r_tok = jnp.take_along_axis(top_idx, r_rank[..., None], -1)[..., 0]
    f_tok = jnp.take_along_axis(top_idx, f_rank[..., None], -1)[..., 0]

    stop = n_acc[:, None]
    bonus_r = jnp.take_along_axis(r_tok, stop, axis=1)[:, 0]
    bonus_f = jnp.take_along_axis(f_tok, stop, axis=1)[:, 0]
    bonus = jnp.where(n_acc < n_draft, bonus_r, bonus_f)

    out = jnp.where(lane < stop, draft_ext, 0)
    out = jnp.where(lane == stop, bonus[:, None], out)
    return out.astype(jnp.int32), (n_acc + 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Generation by diffusion over blocks (models/llama.py BlockDiffusion): a
# forward yields logits for every position of a block; each still-masked
# position draws a candidate, and the most confident ones become known.

RULE_STATIC = "low_confidence_static"
RULE_DYNAMIC = "low_confidence_dynamic"


def transfer_schedule(block: int, steps: int) -> tuple[int, ...]:
    """How many masked positions the static rule makes known at each of a
    block's `steps` denoise forwards: `block // steps`, the first
    `block % steps` forwards one more (the published schedule); the last
    forward takes whatever is left whatever this says."""
    if not 1 <= steps <= block:
        raise ValueError(f"denoise steps must be in 1..{block} (the block "
                         f"length); got {steps}")
    return tuple(block // steps + (i < block % steps) for i in range(steps))


def diffusion_candidates(
    logits: jnp.ndarray,        # [R, S, V] float: a block's logit rows
    key: jax.Array,             # [R] per-slot PRNG keys
    temperature: jnp.ndarray,   # [R] float; 0 => greedy
    top_p: jnp.ndarray,         # [R] float in (0, 1]
    top_k: jnp.ndarray,         # [R] int32
    cap: int = SAMPLING_TOP_CAP,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A candidate token for every position of a block and its confidence
    -> (candidates [R, S] int32, confidence [R, S] float32).

    The candidate is drawn from exactly the distribution `sample_tokens`
    draws from (temperature, top-k, top-p over the top-`cap` window; the
    argmax under temperature 0). Its confidence is the probability the
    model's own distribution gives it: softmax of the temperature-scaled
    logits over the WHOLE vocabulary, before the top-k / top-p cut (greedy
    scales by 1). The published script reads it after the cut, where a
    greedy lane's every candidate has probability 1 and the choice of
    position falls to the tie rule; before the cut a greedy lane unmasks
    what the model is surest of. The plain reference does the same."""
    R, S, V = logits.shape
    cap = min(cap, V)
    logits = logits.astype(jnp.float32)
    masked, top_idx = _masked_top_logits(logits, temperature, top_p, top_k,
                                         cap)            # [R, S, cap] x2
    keys = jax.vmap(lambda k: jax.random.split(k, S))(key)     # [R, S]
    rank = jax.vmap(jax.vmap(
        lambda k, row: jax.random.categorical(k, row)))(keys, masked)
    cand = jnp.take_along_axis(top_idx, rank[..., None], axis=-1)[..., 0]
    # (a kept entry of `masked` is the scaled logit itself)
    chosen = jnp.take_along_axis(masked, rank[..., None], axis=-1)[..., 0]
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None, None]
    total = jax.nn.logsumexp(logits / safe_t, axis=-1)
    return cand.astype(jnp.int32), jnp.exp(chosen - total)


def diffusion_unmask(
    confidence: jnp.ndarray,    # [R, S] float32
    known: jnp.ndarray,         # [R, S] bool: positions already decided
    n_static: jnp.ndarray,      # scalar int32: this forward's static count
    final: jnp.ndarray,         # scalar bool: the block's last forward
    threshold: float | None = None,
) -> jnp.ndarray:
    """Which masked positions become known after this forward -> [R, S]
    bool, never a known one.

    `low_confidence_static` (threshold None): the `n_static` masked
    positions of highest confidence, a tie going to the LOWER position;
    all that are left at the last forward, or where fewer are left.
    `low_confidence_dynamic`: every masked position whose confidence
    exceeds `threshold`, when those are at least the static count; the
    static choice otherwise."""
    masked = ~known
    S = confidence.shape[-1]
    conf = jnp.where(masked, confidence, -jnp.inf)
    pos = jnp.arange(S, dtype=jnp.int32)
    # ahead[r, i, j]: position j is chosen before position i
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (pos[None, None, :] < pos[None, :, None]))
    rank = jnp.sum(ahead & masked[:, None, :], axis=-1)
    take = masked & (rank < n_static)
    if threshold is not None:
        high = masked & (confidence > threshold)
        enough = (jnp.sum(high, axis=-1, keepdims=True)
                  >= jnp.minimum(n_static, jnp.sum(masked, axis=-1,
                                                   keepdims=True)))
        take = jnp.where(enough, high, take)
    return jnp.where(final, masked, take)
