"""What a slot keeps beyond K and V a head at one capacity: ONE table.

The engine, the scheduler, the host and provider/config.py know nothing of
recurrent states, index keys, latent rows, rings or diffusion blocks by
name. They ask this table three things: which settings a model that keeps
such a thing cannot be served under and why (`refusals`), what its
`KVCache.expert_pairs` ends in (`tail_words`), and which rows a config has
(`kept`). A new mechanism adds a ROW here beside its model code; a
subsystem that learns to carry a row's thing deletes that row's reason.

The rows are data. A row's `reasons` are the useful part of a refusal —
why THIS thing cannot ride the block pool, a rolled-back draft, a chunk, the
handoff frame, a mesh — and `SETTINGS` wraps each in the words every row
shares. Importing this file builds the table and nothing else (the
provider asks it before a host is spawned).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

from symmetry_tpu.models.llama import (
    HELD_COUNTS, LATENT_COUNTS, MTP_COUNTS, WINDOW_COUNTS)


class Resident(NamedTuple):
    name: str                       # what the slot keeps
    of: Callable[[Any], Any]        # config -> what it says of it, or None
    phrase: str | None              # what the sentences call such a model
    reasons: dict[str, str]         # setting -> why it cannot carry this
    block: str                      # `stats.engine.<block>` takes its counts
    # the words it appends to `expert_pairs` (the device function that
    # writes them adds to "the last n words"), and what reads them back
    # where they are not plain counts
    words: tuple[str, ...] = ()
    decode: Callable[[Any], dict[str, int]] | None = None
    host_counts: tuple[str, ...] = ()   # what the engine counts on the host

    def counters(self) -> tuple[str, ...]:
        """The names of this row's block, device-counted then host-counted."""
        device = (self.words if self.decode is None
                  else tuple(self.decode((0,) * len(self.words))))
        return device + self.host_counts


def _index_counts(tail) -> dict[str, int]:
    # imported where asked: the module brings Pallas with it, which a
    # provider process that validates a config never needs
    from symmetry_tpu.ops.sparse_attention import read_counts

    return read_counts(tail)


# setting -> (what its sentence opens with, what it tells a {phrase} to do);
# in the order the sentences of one row come out. (An int8 cache is refused
# by the latent row alone: its advice is that row's.)
SETTINGS = {
    "prefix_cache": ("tpu.prefix_cache_mb", "leave it unset for {phrase}"),
    "speculative": ("tpu.speculative", "leave it unset for {phrase}"),
    "prefill_chunk": (
        "tpu.prefill_chunk {value}",
        "set prefill_chunk: null for {phrase} (prompts prefill whole, up "
        "to the largest bucket)"),
    "role": ("tpu.role {value!r}", "{phrase} serves unified"),
    "mesh": ("tpu.mesh", "{phrase} runs on one device"),
    "kv_quant": (
        "tpu.kv_quantization int8",
        "set kv_quantization: null for {phrase} (the row is cached in the "
        "compute dtype)"),
}

RESIDENTS = (
    # a per-slot state that is no row a position (models/hybrid.py: the
    # `ssm` / `conv` leaves), whatever the recurrent kind
    Resident(
        name="recurrent state",
        of=lambda c: (c.recurrent_kind
                      if getattr(c, "layer_types", None) else None),
        phrase="a model with recurrent layers",
        reasons={
            "prefix_cache": (
                "a cached prefix holds K/V rows and no recurrent state, so "
                "a hit would resume the recurrent layers from nothing"),
            "speculative": (
                "a rejected draft is rolled back by lengths alone, and the "
                "recurrent state has already advanced past it"),
            "prefill_chunk": (
                "the chunk programs are not shown to carry the recurrent "
                "state from chunk to chunk"),
            "role": (
                "the KV handoff frame has no place for the recurrent state"),
            "mesh": "the recurrent state has no sharding rules yet",
        },
        block="ssm",
        # valid prompt tokens the recurrent layers scanned, and lanes whose
        # state an insert overwrote
        host_counts=("prefill_tokens", "state_installs")),
    # an index key a cached position beside its K/V row (`KVCache.idx`)
    Resident(
        name="index keys",
        of=lambda c: getattr(c, "sparse", None),
        phrase="a model with sparse attention",
        reasons={
            "prefix_cache": (
                "the block pool knows one entry shape (K, V and their "
                "scales) and would hand a hit back without its index keys"),
            "speculative": (
                "the verify program over a selection is not shown to pick "
                "what the single-position steps pick"),
            "prefill_chunk": (
                "a chunk over a non-empty cache selects on the XLA path "
                "alone ([B, S, T] scores and the threshold's passes through "
                "HBM), which no chip run has driven at long contexts"),
            "role": "the KV handoff frame has no place for the index keys",
            "mesh": (
                "the index cache and the selection have no sharding rules "
                "yet"),
        },
        block="dsa",
        # ops/sparse_attention.py N_COUNTS words: queries, dense queries,
        # then candidates and selected each as a high and a low word
        words=("queries", "dense_queries", "candidates_high",
               "candidates_low", "selected_high", "selected_low"),
        decode=_index_counts),
    # ONE row of rank + rope values a cached position, no K or V a head
    Resident(
        name="latent row",
        of=lambda c: getattr(c, "latent", None),
        phrase="a model with latent attention",
        reasons={
            "prefix_cache": (
                "the block pool knows one entry shape (K, V a head and "
                "their scales) and has no block of latent rows"),
            "speculative": (
                "the verify program over latent rows (several positions a "
                "slot, absorbed) is not shown to pick what the "
                "single-position kernel picks"),
            "prefill_chunk": (
                "a chunk's queries attend over cached latents, for which "
                "there is no flash kernel with a key offset that expands "
                "them block by block (the jnp form holds [chunk, capacity] "
                "float32 scores a head)"),
            "role": (
                "the KV handoff frame carries K and V planes a head and has "
                "no plane for a latent row"),
            "mesh": (
                "the latent row has no head axis to shard and the absorbed "
                "factors have no sharding rules yet"),
            "kv_quant": (
                "a latent row has no head axis for the scale planes and no "
                "int8 form of the absorbed kernel exists"),
        },
        block="mla",
        words=LATENT_COUNTS,
        # prompt tokens prefilled through the expanded form, as dispatched
        host_counts=("prefill_tokens",)),
    # a ring of the window's rows a window layer beside a full row a full
    # layer (`KVCache.kw` / `vw`). A draft is carried: under
    # `tpu.speculative` the ring has the window's rows AND the drafted
    # positions' (`ring_rows`), every row masked by the position it holds,
    # so a rejected position overwrote no key a later query needs
    Resident(
        name="window ring",
        of=lambda c: getattr(c, "window_kind", None),
        phrase="a model with window and full attention layers",
        reasons={
            "prefix_cache": (
                "the block pool knows one entry shape at one capacity, and "
                "a window layer's ring holds a slot's last positions alone, "
                "so a stored prefix would come back without the rows its "
                "window layers need"),
            "prefill_chunk": (
                "a chunk's later positions overwrite ring rows its earlier "
                "queries still need (a ring of the window's rows and a "
                "draft's has no room for a chunk beside it)"),
            "role": (
                "the KV handoff frame carries one K and one V plane at one "
                "capacity and has no place for the rings"),
            "mesh": "the ring leaves have no sharding rules yet",
        },
        block="swa",
        words=WINDOW_COUNTS,
        host_counts=("prefill_tokens",)),        # as dispatched
    # a block of positions denoised together (models/llama.py
    # BlockDiffusion); its block's counts are the engine's and the
    # scheduler's own (`InferenceEngine.diffusion`)
    Resident(
        name="diffusion block",
        of=lambda c: getattr(c, "diffusion", None),
        phrase="a model that generates by diffusion",
        reasons={
            "prefix_cache": (
                "a hit would have to end on a block boundary of the NEW "
                "prompt too (the left-over tokens open the first generated "
                "block), which the radix lookup does not know"),
            "speculative": (
                "a denoise forward already yields several positions a "
                "stream and nothing is drafted ahead of it"),
            "prefill_chunk": (
                "a chunk's last position yields no token here and the "
                "opening block is denoised by the admission program, which "
                "the chunk programs do not do"),
            "role": (
                "the handoff carries one first token, not an opening block"),
            "mesh": (
                "the block programs are not shown equal to the reference on "
                "a sharded trunk"),
        },
        block="diffusion"),
    # a SHARE of the experts the router scores (`experts_held`): no leaf of
    # its own, so it refuses nothing — it counts its hits
    Resident(
        name="held share",
        of=lambda c: getattr(c, "experts_held", None),
        phrase=None, reasons={},
        block="moe",
        words=HELD_COUNTS),
    # a multi-token-prediction module behind the trunk (`mtp_layers`;
    # models/hybrid.py mtp_forward): its block's rows are one more layer of
    # the `k` / `v` leaves, written by the prefill and by every decode
    # step and rolled back with the trunk's by the one length — the
    # engine's on-device drafter (`tpu.speculative: mtp`)
    Resident(
        name="drafting module",
        of=lambda c: getattr(c, "mtp_layers", 0) or None,
        phrase="a model with a multi-token-prediction module",
        reasons={
            "prefix_cache": (
                "a stored prefix would come back without the first draft, "
                "which the module makes from the prompt's last hidden state"),
            "prefill_chunk": (
                "the chunk programs run the trunk alone and would leave "
                "the module's rows of a chunked prompt unwritten"),
            "role": (
                "the handoff carries one first token and no draft to open "
                "the first decode step with"),
            "mesh": "the module's parameters have no sharding rules yet",
        },
        block="mtp",
        # decode steps' drafts scored, drafts accepted, tokens yielded and
        # (slot, step) pairs run, live lanes alone
        words=MTP_COUNTS,
        # prompt tokens the module ran over, as dispatched
        host_counts=("prefill_tokens",)),
)

# the value of `tpu.speculative` that selects the model's own module
MTP = "mtp"


def ring_rows(config, k_draft: int = 0) -> int | None:
    """The rows of a window layer's served ring: the window's, or — where
    drafts of up to `k_draft` positions are verified — the window's and the
    drafts' in whole lane tiles, so that no position a forward writes
    lands on a key its own earlier queries need (`_attention` masks each
    row by the position it holds wherever the ring is not exactly the
    window). None for a model without window layers."""
    window = getattr(config, "window_kind", None) and config.sliding_window
    if not window or not k_draft:
        return window or None
    return -(-(window + k_draft) // 128) * 128


def kept(config) -> tuple[Resident, ...]:
    """The rows `config`'s model has, in the table's order (`row.of(config)`
    is what the config says of each)."""
    return tuple(row for row in RESIDENTS if row.of(config) is not None)


def refusals(config, *, mesh: bool = False, role: str = "unified",
             prefix_cache: bool = False, speculative: bool | str = False,
             prefill_chunk: int | None = None,
             kv_quant: bool = False) -> list[str]:
    """Why `config`'s model cannot be served under these settings: for each
    row it has, in the table's order, one sentence a setting that is on
    (SETTINGS' order) and that the row cannot carry; empty when it can be.
    The engine raises the first as an EngineError; provider/config.py asks
    the same of a preset before anything is built and raises them all as a
    ConfigError."""
    rows = kept(config)
    no_module = []
    if speculative == MTP and not any(r.block == MTP for r in rows):
        no_module = [
            f"tpu.speculative {MTP}: that drafter is a multi-token-"
            f"prediction module among the model's own parameters "
            f"(num_nextn_predict_layers), and this model has none — leave "
            f"it unset, or name the n-gram drafter (true, a number of "
            f"draft tokens or its mapping)"]
    on = {"prefix_cache": prefix_cache or None,
          "speculative": bool(speculative) or None,
          "prefill_chunk": prefill_chunk,
          "role": None if role == "unified" else role,
          "mesh": mesh or None,
          "kv_quant": kv_quant or None}
    return no_module + [
        f"{head.format(value=on[setting])}: {row.reasons[setting]} — "
        f"{advice.format(phrase=row.phrase)}"
        for row in rows
        for setting, (head, advice) in SETTINGS.items()
        if on[setting] is not None and setting in row.reasons]


def tail_words(config) -> tuple[str, ...]:
    """What `config`'s `KVCache.expert_pairs` ends in, behind the
    `num_experts` pair counts: the words of every row that has any, in the
    table's order. A device function adds to ITS row's words (`tail_at`),
    wherever in the tail they lie."""
    return tuple(word for row in kept(config) for word in row.words)


def tail_at(config, words: tuple[str, ...]) -> tuple[int, int]:
    """Where the row whose words are `words` lies in `config`'s
    `expert_pairs`: (start, stop) counted from the vector's START."""
    at = getattr(config, "num_experts", 0)
    for row in kept(config):
        if row.words == tuple(words):
            return at, at + len(words)
        at += len(row.words)
    raise ValueError(f"this model has no row that counts {words!r}")
