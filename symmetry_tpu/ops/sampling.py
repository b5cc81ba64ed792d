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
window is selected in stages (`_top_k`): the maxima of groups of
TOP_K_GROUP_WIDTH consecutive entries choose the `cap` groups that can hold
the top `cap`; the maxima of sub-groups of TOP_K_SUBGROUP_WIDTH consecutive
entries of those 8,192 choose `cap` sub-groups the same way; and only their
2,048 entries are ranked. Exact, ties included, by one argument a stage:
`lax.top_k` is stable (lowest index first), so an entry left out sits behind
`cap` groups whose maxima outrank it — `cap` entries ahead of it; the chosen
groups are gathered in ascending order, so the kept entries lie in
vocabulary order, a stage's groups are runs of consecutive entries of the
stage before, and ties break as in the single call. (Groups that are not
contiguous — strided lanes — would break that rule.) Greedy and any
top_k <= cap are exact; top-p loses only the probability mass beyond the top
`cap` tokens (< 1e-3 for typical LM distributions at cap=64).

On a v5e (my chip runs, PR 55: tools/top_k_ab.py, PERF.md §6): ranking the
8,192 kept entries of one stage whole was 0.22 ms of a 0.90 ms selection at
[128, 152064] but, as a sort over `[128, 4, 8192]`, 4.5 ms of 8.3 at sdar's
[128, 4, 151936] — the largest op of that cell. The form here takes 0.74 ms
and 2.75 ms at those shapes (0.21 at [128, 32768], 0.23 at [64, 128256]):
1.4 ms of the 2.75 is the division by the temperature and the groups'
maxima, passes over the logits that every form makes. Three things carry
it, in the order of what they gave: what a stage ranks is reshaped to flat
rows (`_rows`: a sort over `[128, 4, n]` costs 3.3x the same rows as
`[512, n]`; reshaping the LOGITS to flat rows instead costs a 311 MB
relayout copy, 2 ms), the second stage (sorts of 1,187 + 256 + 2,048 for one
of 8,192), and the map from a kept position back to its group by comparison
with the `cap` chosen numbers instead of an element gather (0.33 ms a stage
at 512 rows, 0.002 so). Sub-groups of 16 and 32 read within 0.01 ms of each
other at 64 and 128 rows and 32 wins by 0.47 ms at 512; 8, three stages
(32 then 8) and first-stage groups of 256 lost everywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.attention import NEG_INF

SAMPLING_TOP_CAP = 64
# Widths of the staged selection's groups — of the vocabulary, then of what
# the first stage kept — and how many groups per kept entry make the stages
# worth taking: the first fixed from a sweep of W in {128, 256, 512} at
# V = 152064 and 32768 on a v5e (PERF.md §6, PR 26), the second from a sweep
# of {8, 16, 32} (and 32 then 8) at every cell's shape (PERF.md §6, PR 55).
TOP_K_GROUP_WIDTH = 128
TOP_K_SUBGROUP_WIDTH = 32
TOP_K_MIN_GROUPS_PER_CAP = 2


def top_k_route(vocab: int, cap: int = SAMPLING_TOP_CAP) -> dict:
    """How `_top_k` selects the top `cap` of `vocab` logits — decided by
    the two static sizes alone, so every call of a served program takes
    the same route and the engine can report it (startup.sampling):
    each stage's groups and width, and how many entries are ranked last."""
    cap = min(cap, vocab)
    stages, n = [], vocab
    for width in (TOP_K_GROUP_WIDTH, TOP_K_SUBGROUP_WIDTH):
        groups = -(-n // width)
        if groups < TOP_K_MIN_GROUPS_PER_CAP * cap:
            break
        stages.append({"groups": groups, "width": width})
        n = cap * width
    if not stages:
        return {"top_k": "direct"}
    return {"top_k": "grouped", "cap": cap, "stages": stages, "ranked": n}


def _rows(x: jnp.ndarray) -> jnp.ndarray:
    """The leading axes as ONE axis of rows, for what a stage ranks: on a
    v5e a sort over `[128, 4, n]` takes a 4-row tile, 3.3x the time of the
    same rows as `[512, n]` (PERF.md §6, PR 55). Only the few hundred values
    a row that a stage ranks are reshaped, never the logits."""
    return x.reshape(-1, x.shape[-1])


def _keep_groups(x: jnp.ndarray, cap: int,
                 width: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One stage: the `cap` groups of `width` consecutive entries that can
    hold the top `cap` of the last axis -> (their entries [..., cap * width]
    in index order, their group numbers [..., cap] ascending). Needs at
    least `cap` groups."""
    *lead, n = x.shape
    groups = -(-n // width)
    pad = groups * width - n
    if pad:  # -inf at the highest indices: behind every real entry
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, pad)],
                    constant_values=-jnp.inf)
    grouped = x.reshape(*lead, groups, width)
    _, chosen = jax.lax.top_k(_rows(grouped.max(-1)), cap)
    # ascending: the kept entries lie in index order
    chosen = jnp.sort(chosen, axis=-1).reshape(*lead, cap)
    kept = jnp.take_along_axis(grouped, chosen[..., None], axis=-2)
    return kept.reshape(*lead, cap * width), chosen


def _grouped_top_k(x: jnp.ndarray, cap: int, widths: tuple[int, ...]
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`lax.top_k(x, cap)` over the last axis, values and indices equal,
    in stages: each width of `widths` keeps `cap` groups of what the stage
    before kept, and only the last stage's cap * widths[-1] entries are
    ranked (module docstring has why it is exact). Values move by whole
    groups (`take_along_axis` over the group axis): an element gather by
    flat index read 11 ms and a one-hot product is inexact at -inf (PR 26);
    only the whole-number map back to the vocabulary is a comparison."""
    kept, stages = x, []
    for width in widths:
        kept, chosen = _keep_groups(kept, cap, width)
        stages.append((chosen, width))
    values, pos = jax.lax.top_k(_rows(kept), cap)
    values = values.reshape(*x.shape[:-1], cap)
    pos = pos.reshape(*x.shape[:-1], cap)
    slot = jnp.arange(cap, dtype=pos.dtype)
    for chosen, width in reversed(stages):
        # A position among a stage's kept entries -> its position among the
        # entries the stage chose from. The group's number is picked out of
        # `chosen` by comparison (whole numbers: exact), which costs a
        # hundredth of the element gather it replaces.
        hit = (pos // width)[..., :, None] == slot
        group = jnp.sum(jnp.where(hit, chosen[..., None, :], 0), axis=-1)
        pos = group * width + pos % width
    return values, pos


def _top_k(x: jnp.ndarray, cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The top `cap` of the last axis, descending, with their indices:
    `lax.top_k`'s result by the route `top_k_route` names."""
    route = top_k_route(x.shape[-1], cap)
    if route["top_k"] == "direct":
        return jax.lax.top_k(x, cap)
    return _grouped_top_k(x, cap,
                          tuple(s["width"] for s in route["stages"]))


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
