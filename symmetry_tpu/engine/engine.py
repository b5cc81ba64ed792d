"""The serving engine: jitted prefill / insert / decode over a slot batch.

Shape discipline (SURVEY §7 hard-part 1 — continuous batching under jit
without recompile storms):

  - PREFILL runs at batch 1, prompt padded to one of a few fixed buckets
    (tpu.prefill_buckets) — one compiled program per bucket, ever.
  - INSERT copies the prefilled KV prefix into slot `i` of the shared decode
    cache with dynamic_update_slice — shapes static, slot index dynamic.
  - DECODE advances ALL slots one token per step at a fixed [B, 1] shape;
    per-slot raggedness lives in position/length arrays, not shapes.

All three are donated-state jits: the decode cache (the big HBM tenant) is
updated in place, never copied. Sampling controls are per-slot device arrays
so one compiled step serves mixed greedy/sampled requests.

The engine is synchronous and single-threaded by design — the asyncio bridge
lives in the scheduler (scheduler.py), mirroring how the reference keeps all
concurrency in one event loop (SURVEY §5.2).
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from symmetry_tpu.models import residents
from symmetry_tpu.models.llama import (
    KVCache,
    ModelConfig,
    absorb_latent,
    cache_logical_axes,
    forward_hidden,
    init_cache,
    init_params,
    kv_row,
    logits_from_hidden,
    preset,
    MTP_COUNTS,
    tail_add,
)
from symmetry_tpu.models.hybrid import mtp_forward


from symmetry_tpu.ops.sampling import (
    RULE_DYNAMIC, RULE_STATIC, diffusion_candidates, diffusion_unmask,
    sample_tokens, top_k_route, transfer_schedule, verify_tokens)
from symmetry_tpu.parallel.mesh import MeshSpec, build_mesh
from symmetry_tpu.parallel.sharding import shardings_for
from symmetry_tpu.engine.prefix_cache import BlockPool, RadixHit, RadixIndex
from symmetry_tpu.engine.spec import SpecConfig
from symmetry_tpu.engine.tokenizer import Tokenizer, get_tokenizer
from symmetry_tpu.utils.trace import Tracer


def _park(state: "DecodeState", park: jnp.ndarray) -> "DecodeState":
    """The head of every decode program: the lanes in `park` ([B] bool:
    released since the last one and not reused, InferenceEngine
    .release_slot) go to length 0. Decode attention reads each lane up to
    its length; a finished request's lane would otherwise be read at its
    whole context, growing a token a step, for nobody."""
    lengths = state.cache.lengths
    return state._replace(cache=state.cache._replace(
        lengths=jnp.where(park, 0, lengths).astype(lengths.dtype)))


def _stay_parked(before: jnp.ndarray, after: jnp.ndarray) -> jnp.ndarray:
    """Per-lane cache lengths after a decode step: a lane at length 0 is
    parked (fresh, or released and not yet reused; a live lane holds its
    prompt) and stays at 0 — it decodes garbage like every idle lane, at
    one position, and never grows into context nobody reads."""
    return jnp.where(before == 0, 0, after)


def _emptied(scratch: KVCache) -> KVCache:
    """A reused prefill buffer under the empty-cache contract: lengths
    position the writes and carry the previous use's values, and
    insert_all adds a prefix's expert count to the decode state's, so a
    reused buffer must not bring its last use's along."""
    cache = scratch._replace(lengths=jnp.zeros_like(scratch.lengths))
    if cache.expert_pairs is not None:
        cache = cache._replace(
            expert_pairs=jnp.zeros_like(cache.expert_pairs))
    return cache


class EngineError(RuntimeError):
    pass


# `DecodeState.draft` of a lane that drafts nothing (any negative value is
# "no draft this step"; this one is also kept from step to step)
DRAFT_OFF = -2


class DecodeState(NamedTuple):
    """Everything the decode step needs, all static-shape device arrays."""

    cache: KVCache            # [L, B, T, K, D] x2 + lengths [B]
    last_token: jnp.ndarray   # [B] int32 — token to feed next step
    temperature: jnp.ndarray  # [B] float32
    top_p: jnp.ndarray        # [B] float32
    top_k: jnp.ndarray        # [B] int32
    rng: jax.Array            # [B] PRNG keys — one stream PER SLOT, seeded
                              # at insert: a seeded request reproduces its
                              # whole completion and no slot's sampling is
                              # perturbed by other traffic
    # Under `tpu.speculative: mtp` alone (None, so no leaf, otherwise):
    # [B] int32, the token the model's multi-token-prediction module
    # drafted to FOLLOW `last_token` — or DRAFT_OFF for a lane whose
    # request opted out, which every step then advances one token
    draft: jnp.ndarray | None = None


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    seed: int | None = None
    # Stream resumption: how many tokens of this request's completion
    # were already sampled (and streamed) before this admission. For a
    # SEEDED request the engine fast-forwards the per-slot PRNG chain by
    # this many draws, so the resumed continuation samples exactly the
    # tokens the uninterrupted run would have — seeded resumes are
    # token-identical, not just greedy ones. Ignored without a seed
    # (unseeded lanes use per-request process entropy, which a new host
    # cannot reproduce anyway; greedy never consults the RNG).
    # Caveat: the one-draw-per-token chain holds for the plain decode
    # path only — a speculative verify dispatch consumes ONE split while
    # emitting several tokens, so seeded SAMPLED identity under
    # tpu.speculative is out of scope (it already isn't reproducible
    # across spec on/off: rejection sampling draws differently); greedy
    # resumes stay exact everywhere because greedy never reads the lane.
    rng_skip: int = 0

    @classmethod
    def from_request(cls, req: Any) -> "SamplingParams":
        return cls(
            temperature=req.temperature if req.temperature is not None else 0.0,
            top_p=req.top_p if req.top_p is not None else 1.0,
            top_k=getattr(req, "top_k", None) or 0,
            seed=req.seed,
        )


@dataclass
class ChunkedPrefill:
    """An in-progress chunked prefill: one prompt's KV prefix being built
    chunk-by-chunk so long-prompt admission never stalls active decode
    streams for more than ~one chunk (round-2 verdict: a 2048-bucket
    prefill froze every stream for ~0.6 s).

    With `start_pos` > 0 the cache was SEEDED from a prefix-cache entry
    (the first start_pos positions already hold that prefix's KV) and
    `ids` carries only the uncached suffix — the chunk loop then covers
    suffix tokens only."""

    slot: int
    ids: np.ndarray           # [1, n_chunks * C] padded suffix tokens
    true_len: int             # FULL prompt length (prefix + suffix)
    n_chunks: int
    cache: Any                # batch-1 prefix KVCache (bucket capacity)
    temp: jnp.ndarray         # [1]
    top_p: jnp.ndarray        # [1]
    top_k: jnp.ndarray        # [1]
    prefill_key: jax.Array    # [1] PRNG for the first-token sample
    decode_key: jax.Array     # [1] PRNG stream carried into decode
    done_chunks: int = 0
    start_pos: int = 0        # tokens already in the cache at start
    full_ids: tuple[int, ...] = ()  # the whole prompt (prefix-store key)

    @property
    def remaining_chunks(self) -> int:
        return self.n_chunks - self.done_chunks

    @property
    def suffix_len(self) -> int:
        return self.true_len - self.start_pos


class InferenceEngine:
    """Owns params + decode state; exposes prefill/insert/decode primitives.

    Thread-safety: NOT thread-safe; exactly one thread (the scheduler's
    engine thread) may call the mutating methods.
    """

    def __init__(
        self,
        config: ModelConfig,
        params: Any,
        tokenizer: Tokenizer,
        *,
        mesh=None,
        max_slots: int = 8,
        max_seq_len: int = 2048,
        prefill_buckets: tuple[int, ...] = (128, 512, 2048),
        cache_dtype=jnp.bfloat16,
        decode_block: int = 1,
        kv_quant: bool = False,
        prefill_chunk: int | None = 256,
        prefill_token_budget: int | None = None,
        prefix_cache_bytes: int = 0,
        prefix_block_tokens: int = 16,
        prefix_gossip_blocks: int = 64,
        prefix_gossip_s: float = 2.0,
        speculative: SpecConfig | None = None,
        fused_dequant: bool = False,
        role: str = "unified",
        diffusion_steps: int | None = None,
        diffusion_threshold: float | None = None,
        tracer: Tracer | None = None,
        compile_watch: Any = None,
    ) -> None:
        self.config = config
        self.params = params
        self.tokenizer = tokenizer
        self.mesh = mesh
        # The start-up's record (PERF.md §3): `from_tpu_config` and
        # `warmup()` stamp their spans on `tracer` — the engine host hands
        # in its own, so READY carries them — and warm-up books to each
        # program what `compile_watch` (utils/devprof.py CompileWatch)
        # counted inside it; without a watch a record's counts are zeros.
        self.tracer = tracer if tracer is not None else Tracer()
        self.compile_watch = compile_watch
        self.warmup_programs: list[dict] = []
        # Where `from_tpu_config`'s last span ended (None for an engine
        # made without it), and where the next warm-up record begins: on
        # that stamp for the warm-up behind the build, else (None) on its
        # own clock read.
        self.built_at: float | None = None
        self._warm_t: float | None = None
        stages = 1 if mesh is None else dict(mesh.shape).get("stage", 1)
        if stages > 1:
            # The axis has no schedule behind it (it leaves parallel/
            # mesh.py AXIS_ORDER with the next mesh change): refused, not
            # served as replication.
            raise EngineError(
                f"mesh axis 'stage' is {stages}: no stage schedule — "
                f"shard over 'model' (and 'data') instead")
        # Disaggregated prefill/decode (engine/disagg/): "unified" is
        # today's engine — prefill AND decode on this chip. "prefill"
        # builds prompt KV and hands it off (never decodes; warmup skips
        # every decode-side compile); "decode" adopts handed-off KV
        # through the prefix store and generates. Role selection changes
        # NO compiled program — it only gates which of the existing
        # programs warmup builds and which scheduler paths run.
        if role not in ("unified", "prefill", "decode"):
            raise EngineError(
                f"unknown engine role {role!r}; expected unified, "
                f"prefill, or decode (disagg is a backend-level role — "
                f"the broker assigns prefill/decode to its two hosts)")
        if role != "unified" and mesh is not None:
            # Handoff frames are host-side numpy snapshots; a sharded
            # cache on a multi-process mesh is not host-addressable.
            # Loud, not silently wrong — same contract as fused_dequant.
            raise EngineError(
                f"tpu.role {role!r} supports single-device engines only "
                f"(KV handoff snapshots the cache host-side); drop the "
                f"role or the mesh")
        self.role = role
        # What a slot of this model keeps beyond K and V a head at one
        # capacity (models/residents.py: a recurrent state, index keys, a
        # latent row, a ring, a diffusion block, a share of the experts),
        # asked ONCE: what cannot carry it is refused here, never served
        # wrong, and each row's counters get their block.
        rows = residents.kept(config)
        refused = residents.refusals(
            config, mesh=mesh is not None, role=role,
            prefix_cache=prefix_cache_bytes > 0,
            speculative=(False if speculative is None
                         else getattr(speculative, "drafter", True)),
            prefill_chunk=prefill_chunk, kv_quant=kv_quant)
        if refused:
            raise EngineError(refused[0])
        # what the PROGRAMS below ask: the state install, the index keys'
        # and the latent row's sizes, the ring's rows, the block length
        found = {row.name: row.of(config) for row in rows}
        self._has_state = "recurrent state" in found
        self._sparse = found.get("index keys")
        self._latent = found.get("latent row")
        self._window = found.get("window ring")
        self._diffusion = found.get("diffusion block")
        # the model's own module drafts inside the decode block
        # (`tpu.speculative: mtp`); its rows are written by every prefill
        # and step only then — served without it, the module is dead weight
        self._mtp = getattr(speculative, "drafter", None) == residents.MTP
        # a window layer's ring: the window's rows, and a draft's where
        # drafts are verified (models/residents.py ring_rows)
        self._ring = residents.ring_rows(
            config, 0 if speculative is None else speculative.k_draft)
        # A parity tool's or a test's window into the programs the module
        # drafts in (None: nothing is traced in): called on the host with
        # ("prefill" | "trunk" | "module", lengths before, logits, ...) from
        # inside the program — set it, then `_build_jits()` again
        self.tap = None
        # since start, a block a row (`stats_blocks`): what the device
        # counted as of the last synced decode block (the words the row
        # appends to `expert_pairs`, `collect_expert_pairs`) and what the
        # host counts as dispatched
        self.counters: dict[str, dict[str, int]] = {
            row.block: dict.fromkeys(names, 0)
            for row in rows if (names := row.counters())}
        self._tails = [(row, residents.tail_at(config, row.words))
                       for row in rows if row.words]
        # Generation by diffusion over blocks (models/llama.py
        # BlockDiffusion): the two generation settings.
        self.diffusion: dict | None = None
        if self._diffusion is None:
            if diffusion_steps is not None or diffusion_threshold is not None:
                raise EngineError(
                    "tpu.diffusion_steps / tpu.diffusion_threshold are the "
                    "settings of a model that generates by diffusion over "
                    "blocks: this model has no block length")
        else:
            block = self._diffusion.block
            if decode_block % block:
                raise EngineError(
                    f"decode_block {decode_block} is no multiple of the "
                    f"block length {block}: a dispatch denoises whole blocks")
            steps = block if diffusion_steps is None else int(diffusion_steps)
            try:
                transfer_schedule(block, steps)
            except ValueError as exc:
                raise EngineError(f"tpu.diffusion_steps: {exc}") from None
            if diffusion_threshold is not None and not (
                    0.0 <= diffusion_threshold < 1.0):
                raise EngineError(
                    f"tpu.diffusion_threshold {diffusion_threshold} is no "
                    f"probability in [0, 1)")
            # a prompt's bucket also holds the opening block behind it
            prefill_buckets = tuple(b for b in prefill_buckets
                                    if b + block <= max_seq_len)
            if any(b % block for b in prefill_buckets):
                raise EngineError(
                    f"every prefill bucket must be a multiple of the block "
                    f"length {block}; got {prefill_buckets}")
            self._bd_steps = steps
            self._bd_threshold = (None if diffusion_threshold is None
                                  else float(diffusion_threshold))
            # since start (stats.engine.diffusion): the engine counts the
            # forwards it dispatches, the scheduler what reached a stream
            self.diffusion = {
                "forwards": 0, "commit_forwards": 0, "admit_forwards": 0,
                "live_slot_forwards": 0, "positions_unmasked": 0,
                "tokens_committed": 0, "tokens_dropped": 0,
                "opening_block_tokens": {str(n): 0
                                         for n in range(1, block + 1)}}
        # W8A16 fused-dequant routing (tpu.fused_dequant): pack the int8
        # weight leaves into the Pallas kernel's tile layout ONCE, here —
        # the layout is the routing (qmatmul dispatches on the leaf
        # type), so every trunk program built below (prefill, chunk,
        # decode, verify) traces fused with no extra knob plumbing, and
        # knob-off leaves every compiled program byte-identical to a
        # build without the feature. On a mesh the pack happens AFTER
        # the sharding decision: pack_params resolves each leaf's
        # contraction/output mesh axes from the same logical-axis tree
        # the dense placement used, picks tile blocks against the
        # per-shard dims, and qmatmul routes the leaf through the
        # shard_map'd per-shard kernel. Leaves that can't shard-pack
        # degrade to the mixed dot — loudly (log + counter), never
        # silently.
        self.fused_dequant = bool(fused_dequant)
        if self.fused_dequant:
            from symmetry_tpu.models.llama import pack_params
            from symmetry_tpu.ops.quant import (
                PackedQuantizedTensor, QuantizedTensor)
            from symmetry_tpu.utils.logging import logger
            from symmetry_tpu.utils.metrics import METRICS, MetricName

            def is_qt(leaf):
                return isinstance(leaf, QuantizedTensor)

            if not any(is_qt(leaf) for leaf in
                       jax.tree.leaves(params, is_leaf=is_qt)):
                raise EngineError(
                    "tpu.fused_dequant found no packable int8 weights — "
                    "it requires tpu.quantization: int8 (the knob would "
                    "otherwise be silently inert)")
            fallback = METRICS.counter(
                MetricName.QMM_FALLBACK,
                "int8 leaves kept on the mixed dot at load",
                labels=("reason",))
            degrades: list[tuple[str, str]] = []
            self.params = params = pack_params(
                params, config=config, mesh=mesh, report=degrades)
            for path, reason in degrades:
                logger.warning(
                    f"tpu.fused_dequant: {path} stays on the mixed "
                    f"dot (reason: {reason})")
                fallback.inc(reason=reason)

            def is_packed(leaf):
                return isinstance(leaf, PackedQuantizedTensor)

            if not any(is_packed(leaf) for leaf in
                       jax.tree.leaves(params, is_leaf=is_packed)):
                logger.warning(
                    "tpu.fused_dequant: no int8 leaf packed on this "
                    "mesh/backend — the engine runs entirely on the "
                    "mixed dot (see the degrade reasons above)")
        self.max_slots = max_slots
        # lanes released since the last decode dispatch and not reused:
        # that dispatch parks them (release_slot)
        self._park = np.zeros((max_slots,), bool)
        self.max_seq_len = max_seq_len
        self.prefill_buckets = tuple(sorted(b for b in prefill_buckets
                                            if b <= max_seq_len))
        if not self.prefill_buckets:
            raise EngineError("no prefill bucket fits within max_seq_len")
        self.cache_dtype = cache_dtype
        self.kv_quant = kv_quant
        if decode_block < 1:
            raise EngineError("decode_block must be >= 1")
        # Prompts that leave less than decode_block headroom finish right
        # after their first token (scheduler admission check), so buckets up
        # to max_seq_len are allowed — they just can't decode far.
        self.decode_block = decode_block
        if prefill_chunk is not None and prefill_chunk < 1:
            raise EngineError("prefill_chunk must be >= 1 (or None)")
        self.prefill_chunk = prefill_chunk
        self.prefill_token_budget = (prefill_token_budget
                                     if prefill_token_budget is not None
                                     else self.PREFILL_TOKEN_BUDGET)
        if self.prefill_token_budget < 1:
            raise EngineError("prefill_token_budget must be >= 1")
        c = config
        # MoE models count the valid (token, expert) pairs each forward
        # computed, per expert, in the caches' `expert_pairs` (models/
        # llama.py KVCache).
        self._count_experts = bool(getattr(c, "num_experts", 0))
        self.expert_pairs = [0] * getattr(c, "num_experts", 0)
        self._pairs_pending: collections.deque = collections.deque()
        self._moe_report: dict | None = None

        if mesh is not None:
            cax = cache_logical_axes(quantized=kv_quant)
            rep = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
            sc = shardings_for(cax.k_scale, mesh) if kv_quant else None
            self._cache_shardings = KVCache(
                k=shardings_for(cax.k, mesh),
                v=shardings_for(cax.v, mesh),
                # lengths stays REPLICATED (O(slots) int32): the host reads
                # individual slots, and on a multi-process data axis a
                # batch-sharded slot may live on another host.
                lengths=rep,
                k_scale=sc, v_scale=sc,
                expert_pairs=rep if self._count_experts else None,
            )
            self._state_shardings = DecodeState(
                cache=self._cache_shardings, last_token=rep, temperature=rep,
                top_p=rep, top_k=rep, rng=rep)
        else:
            self._cache_shardings = None
            self._state_shardings = None

        def _init_state() -> DecodeState:
            return DecodeState(
                cache=init_cache(c, max_slots, max_seq_len, cache_dtype,
                                 quantized=kv_quant,
                                 count_experts=self._count_experts,
                                 # (a window layer's ring, whatever
                                 # the capacity)
                                 **({} if self._window is None
                                    else {"ring": self._ring})),
                last_token=jnp.zeros((max_slots,), jnp.int32),
                temperature=jnp.zeros((max_slots,), jnp.float32),
                top_p=jnp.ones((max_slots,), jnp.float32),
                top_k=jnp.zeros((max_slots,), jnp.int32),
                rng=jax.random.split(jax.random.key(0), max_slots),
                draft=(jnp.full((max_slots,), DRAFT_OFF, jnp.int32)
                       if self._mtp else None),
            )

        if self._state_shardings is not None:
            # Initial placement must match the jits' out_shardings exactly
            # (donated-buffer aliasing on the first insert), and must work
            # when the mesh spans processes — jit-with-out_shardings creates
            # the global arrays in place; device_put of host values cannot
            # address other hosts' devices.
            self.state = jax.jit(_init_state,
                                 out_shardings=self._state_shardings)()
        else:
            self.state = _init_state()

        self._base_key = jax.random.key(
            int.from_bytes(os.urandom(4), "little"))
        self._requests_served = 0
        # (batch, bucket) -> persistent donated prefix buffer; see
        # _prefill_scratch_for.
        self._prefill_scratch: dict[tuple[int, int], Any] = {}

        # Radix-tree prefix cache over a paged KV block pool
        # (prefix_cache.py). `prefix_align` is the compiled SUFFIX width
        # of the one-dispatch hit path (min(prefill_chunk, smallest
        # bucket), unchanged from the aligned-store days); MATCHING now
        # happens at `prefix_block` granularity — any whole-block shared
        # prefix hits, bucket boundaries no longer matter. Off by
        # default (budget 0): the default serving path then performs
        # literally zero extra work — no lookups, no pool allocation,
        # no extra warmup compiles.
        self.prefix_align = (min(self.prefill_chunk, self.prefill_buckets[0])
                             if self.prefill_chunk is not None else None)
        self.prefix_block = int(prefix_block_tokens)
        if self.prefix_block < 1:
            raise EngineError("prefix_block_tokens must be >= 1")
        self.block_pool: BlockPool | None = None
        self.prefix_index: RadixIndex | None = None
        self._pool_kv = None
        # Pool-gossip rider sizing/cadence (tpu.prefix_gossip_blocks /
        # tpu.prefix_gossip_s): how many hot-path block digests the
        # cache summary carries on each stats probe, and the minimum
        # recompute interval (the summary walk is O(digests), but the
        # stats probe fires per heartbeat per member — cache it).
        self.prefix_gossip_blocks = int(prefix_gossip_blocks)
        self.prefix_gossip_s = float(prefix_gossip_s)
        self._gossip_cache: tuple[float, dict | None] | None = None
        if prefix_cache_bytes > 0 and self.prefix_align:
            # Only a BUILT pool constrains the bucket grid (the gather/
            # scatter programs index buckets in whole blocks); with the
            # cache off, prefix_block is only the handoff slicing unit
            # and any bucket set that worked before keeps working.
            for b in self.prefill_buckets:
                if b % self.prefix_block:
                    raise EngineError(
                        f"prefix_block_tokens {self.prefix_block} must "
                        f"divide every prefill bucket (bucket {b} does "
                        f"not) — the block gather/scatter programs "
                        f"index buckets in whole blocks")
            block_bytes = self.prefix_block * self.kv_bytes_per_token()
            n_blocks = int(prefix_cache_bytes) // block_bytes
            if self.role == "decode":
                # Geometry-derived floor, not a fixed MB knob: adoption
                # of a largest-bucket prompt must never be rejected by a
                # default budget too small for the model at hand — the
                # prefill tier's work would ship across the pipe and be
                # thrown away, strictly worse than unified mode. Two
                # largest prefixes' worth keeps one pinned mid-copy
                # while the next adopts.
                n_blocks = max(n_blocks, 2 * (self.prefill_buckets[-1]
                                              // self.prefix_block))
            # The pool must at least hold one smallest-bucket prefix or
            # every insert is a guaranteed rejection.
            n_blocks = max(n_blocks,
                           self.prefill_buckets[0] // self.prefix_block)
            self.block_pool = BlockPool(n_blocks, self.prefix_block,
                                        block_bytes)
            self.prefix_index = RadixIndex(self.block_pool)
        if self.role == "decode" and self.prefix_index is None:
            # Adoption lands handed-off KV through the radix index;
            # without it every migrated request would silently
            # re-prefill from scratch — the exact work the prefill tier
            # already did.
            raise EngineError(
                "role: decode requires the prefix cache "
                "(tpu.prefix_cache_mb > 0 and a prefill_chunk) — "
                "handoff frames are adopted through it")
        if self.role == "prefill" and not self.prefix_align:
            raise EngineError(
                "role: prefill requires tpu.prefill_chunk — the decode "
                "tier's suffix dispatch needs a compiled shape")

        # Speculative decoding (engine/spec/): None keeps the serving path
        # byte-identical — no verify jit is ever built or compiled, the
        # scheduler never drafts, warmup's compile set is unchanged.
        self.spec = speculative
        if self.spec is not None and 1 + self.spec.k_draft > max_seq_len:
            raise EngineError(
                f"speculative k_draft {self.spec.k_draft} does not fit "
                f"max_seq_len {max_seq_len}")

        self._build_jits()

        if self.block_pool is not None:
            # The device half of the pool: one KVCache whose "batch" axis
            # is block ids and whose position capacity is one block —
            # [L, n_blocks + 1, block_tokens, K, D] (+1 for the trash
            # block scatter pads write to). Allocated ONCE here; every
            # insert/evict/adopt thereafter is pointer bookkeeping plus
            # at most one fixed-shape gather or scatter.
            self._pool_kv = self._new_pool_kv()

    def _new_pool_kv(self):
        c = self.config
        slots = self.block_pool.n_blocks + 1  # id 0 is the trash block

        def make():
            return init_cache(c, slots, self.prefix_block,
                              self.cache_dtype, quantized=self.kv_quant,
                              count_experts=self._count_experts)

        if self.mesh is not None:
            return jax.jit(make, out_shardings=self._prefix_shard)()
        return jax.jit(make)()

    # ------------------------------------------------------------------
    # Jitted primitives

    def _build_jits(self) -> None:
        cfg, mesh = self.config, self.mesh

        def prefill(params, tokens, true_len, temp, top_p, top_k, rng,
                    scratch):
            """tokens [N, Sb] padded; returns (first tokens [N], prefix KV).

            N > 1 is COALESCED prefill (scheduler batches concurrent
            arrivals into one dispatch — each dispatch costs a full
            host↔device round-trip, so admission bursts would otherwise
            serialize into p99 TTFT).

            `scratch` is the PERSISTENT prefix buffer for this (batch,
            bucket) shape, donated in and returned as the prefix: a fresh
            init_cache per dispatch allocated+freed the largest transient
            in serving (hundreds of MB per dispatch), and that churn on a
            ~95%-full HBM intermittently wedged mid-traffic prefills in a
            multi-minute allocation retry (round-4 stagger run). The
            prefill-from-empty trunk overwrites EVERY position/scale of
            the buffer (flash attention never reads it), so dirty reuse
            is sound — EXCEPT lengths, which position the writes and
            carry the previous use's values: reset to the empty-cache
            contract first."""
            h, cache = forward_hidden(params, cfg, tokens, _emptied(scratch),
                                      seq_lens=true_len, prefill_flash=True,
                                      tp_mesh=mesh)
            # Project ONLY the last valid position through the LM head —
            # head cost is per-position × vocab, and padded positions are
            # garbage anyway.
            h_last = jnp.take_along_axis(
                h, (true_len - 1)[:, None, None].astype(jnp.int32),
                axis=1)  # [N, 1, E]
            last = logits_from_hidden(params, cfg, h_last)[:, 0]  # [N, V]
            toks = sample_tokens(last, rng, temp, top_p, top_k)  # [N] keys
            if self._mtp:
                # The module over the whole prompt, its rows into its own
                # cache layer: position t reads the trunk's hidden state
                # there and token t + 1 — the sampled first token at the
                # prompt's last position, whose output is the first draft.
                # Returned beside the first token: [N, 2].
                rows = jnp.arange(tokens.shape[0])
                after = jnp.roll(tokens, -1, axis=1).at[
                    rows, true_len - 1].set(toks)
                first, cache = self._module_logits(
                    params, h, after,
                    cache._replace(lengths=jnp.zeros_like(cache.lengths)),
                    true_len, prefill_flash=True)
                draft = jnp.argmax(first, axis=-1)
                if self.tap is not None:
                    jax.debug.callback(self.tap, "prefill", true_len, last,
                                       first, ordered=True)
                toks = jnp.stack([toks, draft.astype(toks.dtype)], axis=1)
            return toks, cache

        def insert(state: DecodeState, prefix: KVCache, row, slot, true_len,
                   first_token, temp, top_p, top_k, rng) -> DecodeState:
            """Copy row `row` of a batch-N prefilled prefix into decode
            slot `slot` (scalars arrive as [N] arrays, indexed by row)."""

            def place(big, small_batch, axis=1):
                # big [L,B,T,...] <- small_batch[:, row] at [:, slot, 0]
                # (KV payloads are rank 5, scale planes rank 4); `axis` is
                # where the batch lies (2 in the conv tails)
                sizes = tuple(1 if d == axis else n
                              for d, n in enumerate(small_batch.shape))
                src = tuple(row if d == axis else 0
                            for d in range(small_batch.ndim))
                small = jax.lax.dynamic_slice(small_batch, src, sizes)
                start = tuple(slot if d == axis else 0
                              for d in range(big.ndim))
                return jax.lax.dynamic_update_slice(
                    big, small.astype(big.dtype), start)

            def place_ring(big, small_batch, axis=2):
                # a window layer's ring [Lw,B,W,...] <- the prompt's LAST
                # W rows of small_batch[:, row] [Lw,1,Sb,...], position p
                # at row p mod W (`axis`: where positions lie; 3 in the
                # scale planes). A bucket no longer than the ring lies as
                # it is, from row 0; of a longer one the W rows that end
                # at the prompt's length are taken, and rolling them by
                # their first position mod W puts each at its row — two
                # contiguous pieces, the ring's tail and its head.
                W, Sb = big.shape[axis], small_batch.shape[axis]
                if Sb <= W:
                    return place(big, small_batch)
                first = jnp.maximum(true_len[row] - W, 0)
                sizes = tuple(1 if d == 1 else W if d == axis else n
                              for d, n in enumerate(small_batch.shape))
                src = tuple(row if d == 1 else first if d == axis else 0
                            for d in range(small_batch.ndim))
                rows = jnp.roll(
                    jax.lax.dynamic_slice(small_batch, src, sizes),
                    first % W, axis=axis)
                start = tuple(slot if d == 1 else 0
                              for d in range(big.ndim))
                return jax.lax.dynamic_update_slice(
                    big, rows.astype(big.dtype), start)

            ring = {}
            if state.cache.kw is not None:
                ring = {"kw": place_ring(state.cache.kw, prefix.kw),
                        "vw": place_ring(state.cache.vw, prefix.vw)}
                if self.kv_quant:
                    ring.update(
                        kw_scale=place_ring(state.cache.kw_scale,
                                            prefix.kw_scale, axis=3),
                        vw_scale=place_ring(state.cache.vw_scale,
                                            prefix.vw_scale, axis=3))
            cache = state.cache._replace(
                **ring,
                k=place(state.cache.k, prefix.k),
                # (a latent cache has no `v` leaf: `k` holds the one row)
                **({"v": place(state.cache.v, prefix.v)}
                   if state.cache.v is not None else {}),
                # The first sampled token's KV is not here yet: the next
                # decode step writes it at position true_len.
                lengths=state.cache.lengths.at[slot].set(true_len[row]),
                **({"k_scale": place(state.cache.k_scale, prefix.k_scale),
                    "v_scale": place(state.cache.v_scale, prefix.v_scale)}
                   if self.kv_quant else {}),
                # The whole recurrent state of the lane is overwritten:
                # this copy is also the lane's reset (a length of 0 is not).
                # (a short convolution's state is its tail: no `ssm` leaf)
                **({"ssm": place(state.cache.ssm, prefix.ssm)}
                   if state.cache.ssm is not None else {}),
                **({"conv": place(state.cache.conv, prefix.conv, axis=2)}
                   if state.cache.conv is not None else {}),
                # a sparse-attention row brings its index keys along
                **({"idx": place(state.cache.idx, prefix.idx)}
                   if state.cache.idx is not None else {}),
            )
            # (a block-diffusion admission hands its opening block over:
            # nothing is fed on from it, the last token fills the field)
            first = (first_token[row] if self._diffusion is None
                     else first_token[row, -1])
            draft = None
            if self._mtp:   # the prefill's [N, 2]: first token, first draft
                first = first_token[row, 0]
                draft = state.draft.at[slot].set(first_token[row, 1])
            return DecodeState(
                draft=draft,
                cache=cache,
                last_token=state.last_token.at[slot].set(first),
                temperature=state.temperature.at[slot].set(temp[row]),
                top_p=state.top_p.at[slot].set(top_p[row]),
                top_k=state.top_k.at[slot].set(top_k[row]),
                # The request's own PRNG stream continues into decode: a
                # seeded request reproduces its whole completion.
                rng=state.rng.at[slot].set(rng[row]),
            )

        def insert_all(state: DecodeState, prefix: KVCache, slots,
                       true_len, first_token, temp, top_p, top_k,
                       rng) -> DecodeState:
            """Install EVERY row of a coalesced prefill in ONE dispatch
            instead of one per row (a dispatch each; how much that costs
            on a local chip is not measured yet — PERF.md). Pad rows
            carry the last real request's slot: re-inserting identical
            data to the same slot is idempotent."""

            def body(i, st):
                return insert(st, prefix, i, slots[i], true_len,
                              first_token, temp, top_p, top_k, rng)

            state = jax.lax.fori_loop(0, slots.shape[0], body, state)
            if state.cache.expert_pairs is not None:
                state = state._replace(cache=state.cache._replace(
                    expert_pairs=(state.cache.expert_pairs
                                  + prefix.expert_pairs)))
            return state

        def insert_from_blocks(scratch: KVCache, pool: KVCache, ids, p):
            """Seed a donated (batch, bucket) working prefix buffer from
            pool blocks: `ids` [bucket // prefix_block] names the block
            covering each bucket position span (pad lanes carry the
            trash block — their gathered garbage lands at positions >= p
            which the suffix continuation never attends), `p` is the
            matched prefix length every row's lengths become. ONE
            compiled program per (batch, bucket) — the ids vector's
            shape is fixed by the bucket, the block ids are data. The
            suffix continuation (chunk_step/chunk_final) then runs from
            these lengths exactly like a chunked prefill that had
            already built p tokens."""
            B = scratch.k.shape[1]

            def gather(parr, big):
                sel = jnp.take(parr, ids, axis=1)      # [L, nb, PB, K, D]
                seq = sel.reshape(
                    (sel.shape[0], 1, sel.shape[1] * sel.shape[2])
                    + sel.shape[3:])
                return jnp.broadcast_to(
                    seq, (seq.shape[0], B) + seq.shape[2:]).astype(big.dtype)

            def gather_scale(parr, big):
                sel = jnp.take(parr, ids, axis=1)      # [L, nb, K, PB]
                sel = jnp.moveaxis(sel, 1, 2)          # [L, K, nb, PB]
                seq = sel.reshape(sel.shape[0], 1, sel.shape[1],
                                  sel.shape[2] * sel.shape[3])
                return jnp.broadcast_to(
                    seq, (seq.shape[0], B) + seq.shape[2:]).astype(big.dtype)

            return scratch._replace(
                k=gather(pool.k, scratch.k),
                v=gather(pool.v, scratch.v),
                lengths=jnp.full_like(scratch.lengths, p),
                **({"k_scale": gather_scale(pool.k_scale, scratch.k_scale),
                    "v_scale": gather_scale(pool.v_scale, scratch.v_scale)}
                   if self.kv_quant else {}),
            )

        def write_blocks(pool: KVCache, row: KVCache, ids):
            """Scatter a batch-1 row buffer (capacity = one bucket) into
            pool blocks: bucket span j lands in pool block ids[j]. Spans
            that should NOT be stored (already-resident prefix blocks,
            positions past the prefix) point their lane at the trash
            block — the scatter stays one fixed shape per bucket and
            unwanted writes go where nobody reads. The pool is donated:
            membership changes in place, never by copy."""
            PB = self.prefix_block

            def put(parr, rarr):
                src = rarr[:, 0].reshape(
                    (rarr.shape[0], ids.shape[0], PB) + rarr.shape[3:])
                return parr.at[:, ids].set(src.astype(parr.dtype))

            def put_scale(parr, rarr):
                src = rarr[:, 0].reshape(rarr.shape[0], rarr.shape[2],
                                         ids.shape[0], PB)
                src = jnp.moveaxis(src, 2, 1)          # [L, nb, K, PB]
                return parr.at[:, ids].set(src.astype(parr.dtype))

            return pool._replace(
                k=put(pool.k, row.k),
                v=put(pool.v, row.v),
                **({"k_scale": put_scale(pool.k_scale, row.k_scale),
                    "v_scale": put_scale(pool.v_scale, row.v_scale)}
                   if self.kv_quant else {}),
            )

        def extract_prefix_row(prefix: KVCache, row, p):
            """Copy row `row` of a batch-N prefill buffer into a FRESH
            batch-1 buffer (the prefix-cache entry) valid through `p`
            tokens. No donation: the output is the newly-allocated entry
            and the source scratch stays pooled."""

            def take(arr):
                sizes = (arr.shape[0], 1) + arr.shape[2:]
                start = (0, row) + (0,) * (arr.ndim - 2)
                return jax.lax.dynamic_slice(arr, start, sizes)

            return KVCache(
                k=take(prefix.k), v=take(prefix.v),
                lengths=jnp.full((1,), p, jnp.int32),
                k_scale=take(prefix.k_scale) if self.kv_quant else None,
                v_scale=take(prefix.v_scale) if self.kv_quant else None,
                # an entry seeds later prefills: it starts their count at 0
                expert_pairs=(None if prefix.expert_pairs is None else
                              jnp.zeros_like(prefix.expert_pairs)),
            )

        def chunk_step(params, tokens, cache, seq_len):
            """Extend a batch-1 prefix cache by one prompt chunk. Attention
            runs the continuation path (absolute-position masking against
            the cache written by earlier chunks) — prefill_flash's
            empty-cache contract doesn't hold past chunk 0."""
            _, cache = forward_hidden(params, cfg, tokens, cache,
                                      seq_lens=seq_len, tp_mesh=mesh)
            return cache

        def chunk_final(params, tokens, cache, seq_len, last_idx,
                        temp, top_p, top_k, rng):
            """Last chunk: also project the final valid position and sample
            the first token (mirrors `prefill`'s tail)."""
            h, cache = forward_hidden(params, cfg, tokens, cache,
                                      seq_lens=seq_len, tp_mesh=mesh)
            h_last = jnp.take_along_axis(
                h, last_idx[:, None, None].astype(jnp.int32), axis=1)
            last = logits_from_hidden(params, cfg, h_last)[:, 0]
            toks = sample_tokens(last, rng, temp, top_p, top_k)
            return toks, cache

        def decode_one(state: DecodeState, params):
            """Advance every slot one token."""
            h, cache = forward_hidden(params, cfg, state.last_token[:, None],
                                      state.cache, tp_mesh=mesh)
            cache = cache._replace(lengths=_stay_parked(
                state.cache.lengths, cache.lengths))
            logits = logits_from_hidden(params, cfg, h)
            split = jax.vmap(lambda k: jax.random.split(k, 2))(state.rng)
            rng, step_key = split[:, 0], split[:, 1]
            toks = sample_tokens(logits[:, 0], step_key, state.temperature,
                                 state.top_p, state.top_k)
            return DecodeState(
                cache=cache, last_token=toks, temperature=state.temperature,
                top_p=state.top_p, top_k=state.top_k, rng=rng,
            ), toks

        def decode_block(params, state: DecodeState, park):
            """K decode steps in ONE dispatch: the per-dispatch host cost
            is paid once per K tokens (SURVEY §7 hard-part 3: streaming
            latency discipline); `park` first (`_park`). Returns (state,
            tokens [K, B], pairs):
            `pairs` is the cache's `expert_pairs` since the last block —
            this block's steps and every prefill inserted in between —
            handed out and zeroed, so the int32 counter never wraps; [0]
            for a model that does not count."""
            state, toks = jax.lax.scan(
                lambda s, _: decode_one(s, params), _park(state, park),
                None, length=self.decode_block)
            pairs = state.cache.expert_pairs
            if pairs is None:
                return state, toks, jnp.zeros((0,), jnp.int32)
            return state._replace(cache=state.cache._replace(
                expert_pairs=jnp.zeros_like(pairs))), toks, pairs

        def mtp_one(state: DecodeState, params):
            """One step of a lane that carries a draft: [last_token,
            draft] through the trunk (the continuation path verify_block
            uses; a window layer's ring has a row for the drafted position
            beside the window's), the draft scored by `verify_tokens`
            unchanged — the module's argmax is a deterministic proposer —
            so 1 or 2 tokens come out; the lengths roll back over a
            rejected position; then the module over the positions that
            stayed (their hidden states, the tokens that came out), whose
            last output is the next draft. A lane without a draft
            (`draft` < 0) advances one token like a plain step."""
            n_draft = (state.draft >= 0).astype(jnp.int32)
            draft = jnp.maximum(state.draft, 0)
            old = state.cache.lengths
            h, cache = forward_hidden(
                params, cfg, jnp.stack([state.last_token, draft], axis=1),
                state.cache, seq_lens=1 + n_draft, tp_mesh=mesh)
            logits = logits_from_hidden(params, cfg, h)       # [B, 2, V]
            split = jax.vmap(lambda q: jax.random.split(q, 2))(state.rng)
            rng, step_key = split[:, 0], split[:, 1]
            out, n_emit = verify_tokens(
                logits, draft[:, None], n_draft, step_key,
                state.temperature, state.top_p, state.top_k)
            last = jnp.take_along_axis(out, (n_emit - 1)[:, None],
                                       axis=1)[:, 0]
            if self.tap is not None:
                jax.debug.callback(self.tap, "trunk", old, logits,
                                   state.draft, out, n_emit, ordered=True)
            proposed, cache = self.device_drafter(
                params, h, out, n_emit, cache._replace(lengths=old))
            live = old > 0
            if cache.expert_pairs is not None:
                def total(x):
                    return jnp.sum(jnp.where(live, x, 0), dtype=jnp.int32)

                cache = cache._replace(expert_pairs=tail_add(
                    cache.expert_pairs, cfg, MTP_COUNTS, jnp.stack([
                        total(n_draft), total(n_emit - 1), total(n_emit),
                        total(1)])))
            cache = cache._replace(
                lengths=_stay_parked(old, old + n_emit))
            return state._replace(
                cache=cache, last_token=last, rng=rng,
                draft=jnp.where(state.draft == DRAFT_OFF, DRAFT_OFF,
                                proposed.astype(jnp.int32))), (out, n_emit)

        def mtp_decode_block(params, state: DecodeState, park):
            """`decode_block` where the module drafts: K steps of 1 or 2
            tokens a lane in ONE dispatch. Returns (state, tokens
            [2K + 1, B], pairs): a lane's tokens packed from row 0 in the
            order they came out, and in the LAST row how many they are."""
            state, (outs, n) = jax.lax.scan(
                lambda s, _: mtp_one(s, params), _park(state, park), None,
                length=self.decode_block)          # [K, B, 2], [K, B]
            K, B = n.shape
            flat = outs.transpose(0, 2, 1).reshape(2 * K, B)
            valid = (jnp.arange(2 * K, dtype=jnp.int32)[:, None] % 2
                     < jnp.repeat(n, 2, axis=0))
            order = jnp.argsort(~valid, axis=0, stable=True)
            toks = jnp.concatenate(
                [jnp.take_along_axis(flat, order, axis=0),
                 jnp.sum(n, axis=0, dtype=jnp.int32)[None]], axis=0)
            pairs = state.cache.expert_pairs
            return state._replace(cache=state.cache._replace(
                expert_pairs=jnp.zeros_like(pairs))), toks, pairs

        def lanes_draft_off(state: DecodeState, slots):
            """The lanes `slots` draft nothing from here on (a request's
            `"speculative": false`)."""
            return state._replace(
                draft=state.draft.at[slots].set(DRAFT_OFF))

        def verify_block(params, state: DecodeState, draft, n_draft, park):
            """Speculative verify: ONE batched forward over [B, 1+k_draft]
            positions — the pending last_token plus every slot's drafted
            continuation — then per-position acceptance (ops/sampling.py
            verify_tokens) and a per-slot cache-length rollback to the
            first rejection. Fixed [B, 1+k] shape: exactly one compiled
            program, covered by warmup only when the knob is on.

            The trunk is the same continuation path chunk_step uses
            (absolute-position causal masking against the live cache), so
            KV for all 1+k positions is appended in place; positions past
            each slot's seq_len write garbage that the rollback lengths
            exclude and later writes overwrite — the rollback itself is
            one lengths update, no data movement. A slot with n_draft 0
            advances exactly one token, like a plain decode step."""
            state = _park(state, park)
            tokens = jnp.concatenate([state.last_token[:, None], draft],
                                     axis=1)               # [B, 1+k]
            seq_lens = 1 + n_draft
            old_lengths = state.cache.lengths
            h, cache = forward_hidden(params, cfg, tokens, state.cache,
                                      seq_lens=seq_lens, tp_mesh=mesh)
            # Head over all 1+k positions: unlike prefill's bucket-wide
            # pad, every lane here is a candidate token — and 1+k is tiny.
            logits = logits_from_hidden(params, cfg, h)    # [B, 1+k, V]
            split = jax.vmap(lambda q: jax.random.split(q, 2))(state.rng)
            rng, step_key = split[:, 0], split[:, 1]
            out, n_emit = verify_tokens(
                logits, draft, n_draft, step_key, state.temperature,
                state.top_p, state.top_k)
            last = jnp.take_along_axis(out, (n_emit - 1)[:, None],
                                       axis=1)[:, 0]
            # Roll back: only the accepted prefix (and the pending bonus
            # token's future write position) stays valid.
            cache = cache._replace(lengths=_stay_parked(
                old_lengths, old_lengths + n_emit))
            return DecodeState(
                cache=cache, last_token=last, temperature=state.temperature,
                top_p=state.top_p, top_k=state.top_k, rng=rng,
            ), out.T, n_emit

        if self._diffusion is not None:
            prefill, decode_block = self._diffusion_programs()
        if self._mtp:
            decode_block = mtp_decode_block
            self._draft_off = jax.jit(lanes_draft_off, donate_argnums=(0,))

        state_shard = self._state_shardings
        if self.mesh is not None:
            # Host-read outputs (sampled tokens) must be fully replicated —
            # on a multi-process mesh np.asarray of a sharded global array
            # is not addressable. The prefill KV prefix keeps the cache's
            # kv_heads-on-model sharding; its batch dim (1) stays unsharded.
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
            # Same rules as the decode cache, minus the batch axis (the
            # prefix has batch 1) — derived from the shared rules table so
            # the layouts can't silently diverge (parallel/sharding.py).
            from symmetry_tpu.parallel.sharding import DEFAULT_RULES

            cax = cache_logical_axes(quantized=self.kv_quant)
            prefix_rules = {**DEFAULT_RULES, "batch": None}
            psc = (shardings_for(cax.k_scale, self.mesh, prefix_rules)
                   if self.kv_quant else None)
            prefix_shard = KVCache(
                k=shardings_for(cax.k, self.mesh, prefix_rules),
                v=shardings_for(cax.v, self.mesh, prefix_rules),
                lengths=rep,
                k_scale=psc, v_scale=psc,
                expert_pairs=rep if self._count_experts else None,
            )
            self._prefix_shard = prefix_shard
            self._prefill = jax.jit(prefill, donate_argnums=(7,),
                                    out_shardings=(rep, prefix_shard))
            self._decode = jax.jit(decode_block, donate_argnums=(1,),
                                   out_shardings=(state_shard, rep, rep))
            if self.spec is not None and not self._mtp:
                self._verify = jax.jit(
                    verify_block, donate_argnums=(1,),
                    out_shardings=(state_shard, rep, rep))
            self._chunk_step = jax.jit(chunk_step, donate_argnums=(2,),
                                       out_shardings=prefix_shard)
            self._chunk_final = jax.jit(chunk_final, donate_argnums=(2,),
                                        out_shardings=(rep, prefix_shard))
            self._insert_from_blocks = jax.jit(
                insert_from_blocks, donate_argnums=(0,),
                out_shardings=prefix_shard)
            self._write_blocks = jax.jit(
                write_blocks, donate_argnums=(0,),
                out_shardings=prefix_shard)
            self._extract_prefix_row = jax.jit(
                extract_prefix_row, out_shardings=prefix_shard)
        else:
            self._prefill = jax.jit(prefill, donate_argnums=(7,))
            self._decode = jax.jit(decode_block, donate_argnums=(1,))
            if self.spec is not None and not self._mtp:
                self._verify = jax.jit(verify_block, donate_argnums=(1,))
            self._chunk_step = jax.jit(chunk_step, donate_argnums=(2,))
            self._chunk_final = jax.jit(chunk_final, donate_argnums=(2,))
            self._insert_from_blocks = jax.jit(insert_from_blocks,
                                               donate_argnums=(0,))
            self._write_blocks = jax.jit(write_blocks, donate_argnums=(0,))
            self._extract_prefix_row = jax.jit(extract_prefix_row)
        self._insert_all = jax.jit(
            insert_all, donate_argnums=(0,),
            out_shardings=state_shard)

        def rng_resume(key, skip):
            """Fast-forward one request's PRNG chain past `skip` draws
            (stream resumption): replays the exact split sequence the
            serving path performs — prefill consumes the first split's
            key, every decode step re-splits the carry — so the returned
            (prefill key, decode key) put a resumed seeded request at
            the same chain position an uninterrupted run would occupy
            after `skip` sampled tokens. `skip` is DATA (fori_loop trip
            count), so one compiled program covers every resume depth —
            no per-length recompile."""
            pk, dk = jax.random.split(key)

            def body(_, carry):
                dk, _pk = carry
                s = jax.random.split(dk)
                return s[0], s[1]

            dk, pk = jax.lax.fori_loop(0, skip, body, (dk, pk))
            return pk, dk

        # Scalar key program, mesh-independent (keys are replicated).
        self._rng_resume = jax.jit(rng_resume)

        def derive_keys(base_key, counters, seeds, seeded):
            """What _request_keys does for one request (without a resume
            skip), for a whole prefill batch in one dispatch: the
            request's own seed, or its counter folded into the base key,
            then the split into (prefill, decode)."""
            def one(counter, seed, is_seeded):
                data = jnp.where(
                    is_seeded,
                    jax.random.key_data(jax.random.key(seed)),
                    jax.random.key_data(
                        jax.random.fold_in(base_key, counter)))
                return jax.random.split(jax.random.wrap_key_data(data))

            pairs = jax.vmap(one)(counters, seeds, seeded)
            return pairs[:, 0], pairs[:, 1]

        self._derive_keys = jax.jit(derive_keys)

    def _diffusion_programs(self):
        """The admission and the decode program of a model that generates
        by diffusion over blocks (models/llama.py BlockDiffusion), under
        the names a device trace is cut by: `bd_prefill`, `bd_decode_block`.

        Both denoise a block the same way (`denoise`): `steps` forwards over
        the block's [rows, block] tokens through the continuation path —
        the block's K/V written in place behind the committed context and
        `cache.lengths` left where it was, so nothing is committed — each
        followed by the candidates, their confidences and the choice of
        which masked positions become known (ops/sampling.py); then ONE
        more forward over the finished block, which moves `lengths` by the
        block: the commit. Known positions are a plane of their own: a
        sampled id equal to the mask token's is a token like any other."""
        cfg, mesh = self.config, self.mesh
        block, mask_id = self._diffusion.block, self._diffusion.mask_token_id
        steps, threshold = self._bd_steps, self._bd_threshold
        schedule = transfer_schedule(block, steps)

        def denoise(params, cache, tokens, known, temp, top_p, top_k, rng):
            """tokens / known [R, block] at each row's `cache.lengths` (a
            block boundary) -> (cache with the block committed, its tokens
            [R, block], rng)."""
            base = cache.lengths
            counts = jnp.asarray(schedule, jnp.int32)

            def step(i, carry):
                cache, tokens, known, rng = carry
                h, cache = forward_hidden(params, cfg, tokens, cache,
                                          tp_mesh=mesh)
                cache = cache._replace(lengths=base)  # not committed
                logits = logits_from_hidden(params, cfg, h)  # [R, block, V]
                split = jax.vmap(lambda k: jax.random.split(k, 2))(rng)
                cand, conf = diffusion_candidates(
                    logits, split[:, 1], temp, top_p, top_k)
                take = diffusion_unmask(conf, known, counts[i],
                                        i == steps - 1, threshold)
                return (cache, jnp.where(take, cand, tokens), known | take,
                        split[:, 0])

            cache, tokens, _, rng = jax.lax.fori_loop(
                0, steps, step, (cache, tokens, known, rng))
            _, cache = forward_hidden(params, cfg, tokens, cache,
                                      tp_mesh=mesh)  # the commit
            return cache, tokens, rng

        def bd_prefill(params, tokens, true_len, temp, top_p, top_k, rng,
                       scratch):
            """tokens [N, Sb] padded; returns (opening blocks [N, block],
            prefix KV). The prompt's whole blocks are prefilled under the
            block mask (`prefill`'s contract for `scratch`, whose capacity
            is the bucket plus one block); its `true_len % block` left-over
            tokens open the first generated block as known positions, and
            that block is denoised and committed HERE, so the lane enters
            decode at a block boundary: row i's first tokens are
            `out[i, true_len[i] % block:]`, between 1 and `block` of them."""
            whole = true_len // block * block
            _, cache = forward_hidden(params, cfg, tokens, _emptied(scratch),
                                      seq_lens=whole, prefill_flash=True,
                                      tp_mesh=mesh)
            pos = whole[:, None] + jnp.arange(block, dtype=jnp.int32)[None]
            known = pos < true_len[:, None]
            left_over = jnp.take_along_axis(
                tokens, jnp.minimum(pos, tokens.shape[1] - 1), axis=1)
            opening = jnp.where(known, left_over, mask_id).astype(jnp.int32)
            cache, out, _ = denoise(params, cache, opening, known, temp,
                                    top_p, top_k, rng)
            return out, cache

        def bd_decode_block(params, state: DecodeState, park):
            """`decode_block / block` blocks a slot in ONE dispatch, each
            denoised from all-masked and committed; `decode_block`'s
            contract otherwise: (state, tokens [K, B] in position order,
            pairs)."""
            rows = self.max_slots

            def one(s: DecodeState, _):
                before = s.cache.lengths
                cache, toks, rng = denoise(
                    params, s.cache,
                    jnp.full((rows, block), mask_id, jnp.int32),
                    jnp.zeros((rows, block), bool), s.temperature, s.top_p,
                    s.top_k, s.rng)
                cache = cache._replace(
                    lengths=_stay_parked(before, cache.lengths))
                return s._replace(cache=cache, last_token=toks[:, -1],
                                  rng=rng), toks.T

            state, toks = jax.lax.scan(
                one, _park(state, park), None,
                length=self.decode_block // block)
            toks = toks.reshape(self.decode_block, rows)
            pairs = state.cache.expert_pairs
            if pairs is None:
                return state, toks, jnp.zeros((0,), jnp.int32)
            return state._replace(cache=state.cache._replace(
                expert_pairs=jnp.zeros_like(pairs))), toks, pairs

        return bd_prefill, bd_decode_block

    # ------------------------------------------------------------------
    # Host-side API (called by the scheduler's engine thread)

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise EngineError(
            f"prompt of {prompt_len} tokens exceeds the largest prefill "
            f"bucket ({self.prefill_buckets[-1]})")

    # Coalesced-prefill batch sizes: one compiled prefill program per
    # (batch, bucket) pair, so batch is bucketed too. The batch width is
    # gated PER BUCKET by a token budget (batch × bucket ≤ budget): wide
    # batches at the small buckets — a 128-client burst of 128-token
    # prompts is 8 dispatches at batch 16 instead of 32 at batch 4, the
    # direct driver of burst TTFT — while the big buckets stay narrow so
    # the transient prefill buffers never tip the HBM budget (round-2's
    # flat batch-8-at-every-bucket attempt OOM'd the llama3-8b@128-slot
    # config; batch 4 × 2048 tokens was the peak, not batch 8 × 128).
    PREFILL_BATCHES = (1, 2, 4, 8, 16)
    PREFILL_TOKEN_BUDGET = 2048

    def prefill_batches_for(self, bucket: int) -> tuple[int, ...]:
        """Allowed coalesced-prefill batch sizes at `bucket` (ascending,
        always contains 1). Capped by max_slots: a batch wider than the
        slot count could be SELECTED at runtime (next-largest padding) but
        is never compiled by warmup — the resulting mid-traffic XLA
        compile is the exact stall warmup exists to prevent."""
        budget = max(self.prefill_token_budget, bucket)
        return tuple(b for b in self.PREFILL_BATCHES
                     if b * bucket <= budget
                     and (b == 1 or b <= min(self.max_slots,
                                             self._state_rows_max())))

    # What one prefill buffer may hold of recurrent state: every ROW of a
    # buffer carries a slot's whole state whatever its bucket (37.7 MB at
    # granite-4.0-h-small: 4 rows), and the prefill program holds a second
    # copy of it in the layout its state einsum writes. Narrow batches also
    # keep an admission dispatch short beside a decode block: the per-block
    # admission budget is checked between dispatches, so the longest gap a
    # stream sees is a block plus one dispatch past the budget (PERF.md,
    # PR 33: at 8 rows `gap_p99_s` spread 10% over six seeds).
    STATE_SCRATCH_BYTES = 160 << 20

    def _state_rows_max(self) -> int:
        """Widest prefill batch a model with recurrent layers takes, from
        the shape alone; the widest there is for any other model."""
        per_row = self.state_bytes_per_slot()
        if not per_row:
            return self.PREFILL_BATCHES[-1]
        return max(1, min(self.PREFILL_BATCHES[-1],
                          self.STATE_SCRATCH_BYTES // per_row))

    def _request_keys(self, sampling: SamplingParams) -> tuple[Any, Any]:
        """(prefill key, decode key) for one request: seeded requests
        reproduce their whole completion; unseeded ones get per-request
        entropy. ONE derivation shared by every admission path, so a
        seeded request samples identically whether it was admitted via
        full prefill, chunked prefill, or a prefix-cache hit.

        `rng_skip` (stream resumption) fast-forwards a SEEDED request's
        chain past the draws its interrupted run already made: the
        uninterrupted run samples token 1 from the prefill key and token
        i+1 from the i-th decode split, so a resume after N emitted
        tokens needs prefill key = the N-th step key and decode key =
        the N-th carry — exactly what _rng_resume walks to."""
        skip = max(0, int(sampling.rng_skip or 0))
        if sampling.seed is not None:
            key = jax.random.key(sampling.seed)
            if skip:
                pk, dk = self._rng_resume(key, skip)
                return pk, dk
        else:
            self._requests_served += 1
            key = jax.random.fold_in(self._base_key, self._requests_served)
        pk, dk = jax.random.split(key)
        return pk, dk

    def _group_keys(self, samplings: list[SamplingParams], batch: int
                    ) -> tuple[jax.Array, jax.Array]:
        """(prefill keys, decode keys), [batch] each, for one coalesced
        admission dispatch: row i is _request_keys(samplings[i]), rows
        past the group replay the last request's (pad rows must be exact
        overwrites). ONE device dispatch for the whole batch: one at a
        time each request costs half a dozen tiny programs (key or
        fold_in, split, two index ops, its share of two stacks), and
        past a few dozen computations in flight the runtime makes the
        dispatching thread wait for the program at the head of the
        queue — the decode block admission is meant not to wait for.
        Only a resumed seeded request (rng_skip) is walked on its own."""
        n_req = len(samplings)
        counters = np.zeros((batch,), np.int32)
        seeds = np.zeros((batch,), np.int64)
        seeded = np.zeros((batch,), bool)
        resumed: dict[int, tuple[Any, Any]] = {}
        for i, sampling in enumerate(samplings):
            if sampling.seed is None:
                self._requests_served += 1
                counters[i] = self._requests_served
                continue
            seeds[i], seeded[i] = sampling.seed, True
            if sampling.rng_skip:
                resumed[i] = self._request_keys(sampling)
        for arr in (counters, seeds, seeded):
            arr[n_req:] = arr[n_req - 1]
        # int64 -> int32 wraps like jax.random.key's own conversion of a
        # Python int does without x64.
        prefill_keys, decode_keys = self._derive_keys(
            self._base_key, counters, seeds.astype(np.int32), seeded)
        for i, (pk, dk) in resumed.items():
            rows = slice(i, batch if i == n_req - 1 else i + 1)
            prefill_keys = prefill_keys.at[rows].set(pk)
            decode_keys = decode_keys.at[rows].set(dk)
        return prefill_keys, decode_keys

    # Every admission path comes in two forms. The DISPATCH form enqueues
    # its programs (prefill or final chunk, then insert) and returns the
    # sampled first tokens as the device array, unread: the scheduler
    # reads it when it reaches the entry in its in-flight queue, in
    # device order, so the engine thread never waits inside admission.
    # The plain form is "dispatch, then read" for every other caller.

    def prefill_and_insert(self, slot: int, prompt_ids: list[int],
                           sampling: SamplingParams) -> int:
        """Prefill a prompt and install it in `slot`; returns first token
        (waits for the device)."""
        return self.prefill_and_insert_many(
            [(slot, prompt_ids, sampling)])[0]

    def prefill_and_insert_many(
        self, assignments: list[tuple[int, list[int], SamplingParams]],
    ) -> list[int]:
        """Prefill several prompts in as few device dispatches as the
        bucket's batch budget allows and install each in its slot; returns
        their first tokens (waits for the device). Coalescing matters
        because each dispatch pays a host↔device round-trip: admitting a
        burst of arrivals one-by-one serializes that cost into the last
        request's TTFT (SURVEY §7 hard-part 3). A group wider than the
        bucket's largest allowed batch is split into consecutive
        dispatches."""
        if not assignments:
            return []
        if any(len(ids) == 0 for _, ids, _ in assignments):
            raise EngineError("empty prompt")
        bucket = max(self.bucket_for(len(ids)) for _, ids, _ in assignments)
        cap = self.prefill_batches_for(bucket)[-1]
        firsts: list[int] = []
        for start in range(0, len(assignments), cap):
            part = assignments[start:start + cap]
            toks = np.asarray(self.prefill_and_insert_many_dispatch(part))
            if self._mtp:       # [N, 2]: the first token, the first draft
                toks = toks[:, 0]
            firsts.extend(int(tok) for tok in toks[:len(part)])
        return firsts

    def prefill_and_insert_many_dispatch(
        self, assignments: list[tuple[int, list[int], SamplingParams]],
    ) -> jax.Array:
        """Dispatch ONE coalesced prefill and the insert that installs
        every row in its slot; returns the first tokens [batch] on the
        device, unread — row i is assignments[i]'s, rows past the group
        are padding. The group must fit the bucket's largest batch."""
        if any(len(ids) == 0 for _, ids, _ in assignments):
            raise EngineError("empty prompt")
        n_req = len(assignments)
        bucket = max(self.bucket_for(len(ids)) for _, ids, _ in assignments)
        allowed = self.prefill_batches_for(bucket)
        if not 0 < n_req <= allowed[-1]:
            raise EngineError(
                f"prefill group of {n_req} outside the bucket's batch cap "
                f"{allowed[-1]} (the caller partitions to cap)")
        batch = next(b for b in allowed if b >= n_req)

        padded = np.zeros((batch, bucket), np.int32)
        lens = np.zeros((batch,), np.int32)
        temps = np.zeros((batch,), np.float32)
        top_ps = np.ones((batch,), np.float32)
        top_ks = np.zeros((batch,), np.int32)
        slots_arr = np.zeros((batch,), np.int32)
        for i in range(batch):
            # Pad rows replay the last request BIT-IDENTICALLY — same
            # prompt, same slot, and (_group_keys) the same PRNG keys.
            # They are inserted (insert_all covers every row), so anything
            # short of an identical overwrite would corrupt the last real
            # slot's state: a pad row with fresh entropy would sample a
            # DIFFERENT first token and leave decode conditioned on a
            # token the client never saw.
            slot, ids, sampling = assignments[min(i, n_req - 1)]
            slots_arr[i] = slot
            padded[i, :len(ids)] = ids
            lens[i] = len(ids)
            temps[i] = sampling.temperature
            top_ps[i] = sampling.top_p
            top_ks[i] = sampling.top_k
        prefill_keys, decode_keys_arr = self._group_keys(
            [sampling for _, _, sampling in assignments], batch)

        for block in self.counters.values():
            if "prefill_tokens" in block:
                block["prefill_tokens"] += int(lens[:n_req].sum())
        lens_arr = jnp.asarray(lens)
        temps_arr = jnp.asarray(temps)
        top_ps_arr = jnp.asarray(top_ps)
        top_ks_arr = jnp.asarray(top_ks)
        toks, prefix = self._prefill(
            self.params, jnp.asarray(padded), lens_arr, temps_arr,
            top_ps_arr, top_ks_arr, prefill_keys,
            self._prefill_scratch_for(batch, bucket))
        if self._diffusion is not None:
            # the lanes enter decode with their opening block committed
            block = self._diffusion.block
            lens_arr = jnp.asarray(lens // block * block + block)
            self.diffusion["admit_forwards"] += self._bd_steps + 1
        # One dispatch installs every row; pad rows re-write the last
        # real slot with bit-identical data (same prompt AND keys above).
        self._insert(prefix, slots_arr, lens_arr, toks, temps_arr,
                     top_ps_arr, top_ks_arr, decode_keys_arr)
        # Populate the prefix cache from this batch BEFORE the buffer goes
        # back to the pool (the extract reads it; the next same-shape
        # prefill would overwrite it).
        if self.prefix_index is not None:
            self.prefix_index.note_miss(n_req)  # admitted uncached
            self._maybe_store_prefix(assignments[:n_req], prefix)
        # insert_all READS prefix (no donation): the buffer is free for
        # the next same-shape prefill the moment the insert executes —
        # device-order sequencing makes immediate reuse safe.
        self._store_prefill_scratch(batch, bucket, prefix)
        # (where the module drafts this is the prefill's [N, 2] — the first
        # token and the first draft, which the insert took on the device —
        # unread: a slice here would be a program of its own a batch size,
        # compiled in the middle of traffic. The reader takes column 0.)
        return toks

    def device_drafter(self, params, h, out, n_emit, cache):
        """The seam a drafter that runs INSIDE the decode program fills
        (engine/spec/: the host-side n-gram drafter's counterpart), traced
        into every step of `mtp_decode_block`: `h` [B, 2, E] the trunk's
        hidden states at the step's two positions, `out` [B, 2] the tokens
        that came out, of which `n_emit` [B] stay, `cache` at the lengths
        BEFORE the step. Returns (the token each lane drafts to follow its
        last one [B], the cache with whatever rows the drafter keeps
        written — the engine sets the lengths). Here: the model's
        multi-token-prediction module over the positions that stayed, its
        argmax at the last of them."""
        logits, cache = self._module_logits(params, h, out, cache, n_emit)
        if self.tap is not None:
            jax.debug.callback(self.tap, "module", cache.lengths - n_emit,
                               logits, n_emit, ordered=True)
        return jnp.argmax(logits, axis=-1), cache

    def _module_logits(self, params, h, after, cache, n, *,
                       prefill_flash: bool = False):
        """The module over `n` [B] positions a lane from `cache.lengths`
        (`h` the trunk's hidden states there, `after` the token after
        each): (its logits at the LAST of them [B, V], the cache with its
        rows written and the lengths advanced)."""
        hm, cache = mtp_forward(params, self.config, h, after, cache,
                                seq_lens=n, prefill_flash=prefill_flash)
        hm_last = jnp.take_along_axis(
            hm, (n - 1)[:, None, None].astype(jnp.int32), axis=1)
        return logits_from_hidden(params, self.config, hm_last)[:, 0], cache

    def draft_off(self, slot: int) -> None:
        """Lane `slot`'s request opted out of drafting (`"speculative":
        false`): its steps advance one token. Nothing to do unless the
        model's own module drafts."""
        if self._mtp:
            self.state = self._draft_off(
                self.state, jnp.asarray([slot], jnp.int32))

    # ------------------------------------------------------------------
    # Shared-prefix KV cache (engine side; bookkeeping in prefix_cache.py)

    def prefix_lookup(self, prompt_ids: list[int]) -> RadixHit | None:
        """Pinned longest block-aligned prefix hit for this prompt, or
        None. The scheduler partitions admission groups by the hit's
        (node, matched_len) group key (hit/miss requests become separate
        dispatch units) and must release() hits it ends up not
        dispatching; the engine releases hits it consumes."""
        if self.prefix_index is None:
            return None
        return self.prefix_index.lookup(prompt_ids)

    def _bucket_ids(self, bucket: int, blocks=(), at: int = 0):
        """Padded block-id lane vector for one bucket's gather/scatter:
        lane j covers bucket positions [j*PB, (j+1)*PB). Lanes outside
        `blocks` (placed starting at block lane `at`) carry the trash
        block — gathers from it are never attended, scatters to it are
        never read. Fixed shape per bucket: ids are data, not shape."""
        ids = np.zeros((bucket // self.prefix_block,), np.int32)
        if len(blocks):
            ids[at:at + len(blocks)] = blocks
        return jnp.asarray(ids)

    def seeded_chunk_ok(self, prompt_len: int) -> bool:
        """True when a LONG-suffix hit (suffix > prefix_align) can run as
        a seeded chunked prefill: the chunk programs for this prompt's
        bucket exist only when the bucket exceeds one chunk (warmup
        compiles exactly that set). Otherwise the hit must fall back to a
        plain full prefill — never a mid-traffic XLA compile."""
        return (self.prefill_chunk is not None
                and self.bucket_for(prompt_len) > self.prefill_chunk)

    def prefill_and_insert_cached(
        self, assignments: list[tuple[int, list[int], SamplingParams]],
        hit: RadixHit,
    ) -> list[int]:
        """prefill_and_insert_cached_dispatch, then read: the group's
        first tokens (waits for the device)."""
        toks = self.prefill_and_insert_cached_dispatch(assignments, hit)
        return [int(tok) for tok in np.asarray(toks)[:len(assignments)]]

    def prefill_and_insert_cached_dispatch(
        self, assignments: list[tuple[int, list[int], SamplingParams]],
        hit: RadixHit,
    ) -> jax.Array | list:
        """Admit a group of requests that SHARE a cached prefix: one
        block gather seeds every row of the (batch, bucket) working
        buffer straight from the pool, one continuation dispatch
        prefills only the uncached suffixes (<= prefix_align tokens
        each, the compiled suffix shape) and samples first tokens, one
        insert installs every slot — three dispatches for the whole
        group regardless of how long the shared prefix is. The finished
        rows then extend the radix tree with their NEW tail blocks, so
        the next turn of the same session hits at its full history.
        Returns the first tokens [batch] on the device, unread (row i is
        assignments[i]'s). Releases `hit` in all paths."""
        try:
            if not assignments:
                return []
            p = hit.length
            A = self.prefix_align
            n_req = len(assignments)
            bucket = max(self.bucket_for(len(ids))
                         for _, ids, _ in assignments)
            allowed = self.prefill_batches_for(bucket)
            if n_req > allowed[-1]:
                raise EngineError(
                    f"cached-prefill group of {n_req} exceeds the bucket's "
                    f"batch cap {allowed[-1]} (scheduler partitions to cap)")
            for _, ids, _ in assignments:
                if not p < len(ids) <= p + A:
                    raise EngineError(
                        f"cached-prefill suffix out of range: prompt "
                        f"{len(ids)} vs prefix {p} (suffix cap {A})")
                if tuple(ids[:p]) != hit.tokens:
                    raise EngineError("prompt diverges from cached prefix")
            batch = next(b for b in allowed if b >= n_req)

            suffix = np.zeros((batch, A), np.int32)
            sfx_lens = np.zeros((batch,), np.int32)
            full_lens = np.zeros((batch,), np.int32)
            temps = np.zeros((batch,), np.float32)
            top_ps = np.ones((batch,), np.float32)
            top_ks = np.zeros((batch,), np.int32)
            slots_arr = np.zeros((batch,), np.int32)
            for i in range(batch):
                # Pad rows replay the last request bit-identically (same
                # suffix, slot, and keys) — same contract as the full
                # prefill path: every row is inserted, so a pad row must
                # be an exact overwrite of the last real slot.
                slot, ids, sampling = assignments[min(i, n_req - 1)]
                sfx = ids[p:]
                suffix[i, :len(sfx)] = sfx
                sfx_lens[i] = len(sfx)
                full_lens[i] = len(ids)
                temps[i] = sampling.temperature
                top_ps[i] = sampling.top_p
                top_ks[i] = sampling.top_k
                slots_arr[i] = slot
            prefill_keys, decode_keys_arr = self._group_keys(
                [sampling for _, _, sampling in assignments], batch)

            scratch = self._prefill_scratch_for(batch, bucket)
            scratch = self._insert_from_blocks(
                scratch, self._pool_kv, self._bucket_ids(bucket, hit.blocks),
                jnp.int32(p))
            # The gather out of the pool is dispatched (device order is
            # FIFO, so any later scatter into a since-freed block runs
            # after this read): safe to unpin now.
            hit.release()
            sfx_arr = jnp.asarray(sfx_lens)
            temps_arr = jnp.asarray(temps)
            top_ps_arr = jnp.asarray(top_ps)
            top_ks_arr = jnp.asarray(top_ks)
            toks, prefix = self._chunk_final(
                self.params, jnp.asarray(suffix), scratch, sfx_arr,
                sfx_arr - 1, temps_arr, top_ps_arr, top_ks_arr,
                prefill_keys)
            self._insert(prefix, slots_arr, jnp.asarray(full_lens), toks,
                         temps_arr, top_ps_arr, top_ks_arr, decode_keys_arr)
            # The finished rows hold prefix + suffix KV: extend the tree
            # with the new tail blocks BEFORE the buffer goes back to
            # the scratch pool — this is what makes turn N+1 of a
            # session hit at its FULL history instead of re-prefilling
            # the part turn N added.
            self._maybe_store_prefix(assignments[:n_req], prefix)
            self._store_prefill_scratch(batch, bucket, prefix)
            self.prefix_index.note_reuse(n_req, p)
            return toks
        finally:
            hit.release()

    def _maybe_store_prefix(self, assignments, prefix) -> None:
        """Store ONE newly-built prefix from a prefill batch into the
        pool (at most one extract + one scatter dispatch per admission
        dispatch, so cache population cannot balloon admission latency).
        The stored row is the first whose whole-block prefix has an
        unresident tail; only the NEW blocks are scattered — blocks the
        radix tree already holds stay shared by reference, and their
        scatter lanes point at the trash block."""
        PB = self.prefix_block
        for row, (_slot, ids, _sampling) in enumerate(assignments):
            p = PB * (len(ids) // PB)
            if p < PB:
                continue
            plan = self.prefix_index.plan_insert(ids[:p])
            if plan is None:
                continue  # fully resident, or rejected even after LRU
            try:
                # Inside the try: a device failure in the extract (or
                # anywhere before commit) must abort the plan, or its
                # pinned prefix and allocated blocks leak forever.
                row_cache = self._extract_prefix_row(
                    prefix, jnp.int32(row), jnp.int32(p))
                bucket = row_cache.k.shape[2]
                lane0 = plan.matched_len // PB
                self._pool_kv = self._write_blocks(
                    self._pool_kv, row_cache,
                    self._bucket_ids(bucket, plan.new_ids, at=lane0))
            except Exception:
                plan.abort()
                raise
            plan.commit()
            return

    def prefix_cache_stats(self) -> dict | None:
        return (self.prefix_index.stats()
                if self.prefix_index is not None else None)

    def prefix_cache_summary(self) -> dict | None:
        """Compact radix-cache summary for pool gossip (see
        RadixIndex.summary) — recomputed at most every
        `prefix_gossip_s` seconds so per-member heartbeat probes share
        one walk. None when the cache or the gossip rider is off.
        Called from the host's serve (stats) thread; the summary walk
        itself is read-only and exception-guarded."""
        if self.prefix_index is None or self.prefix_gossip_blocks <= 0:
            return None
        now = time.monotonic()
        cached = self._gossip_cache
        if cached is not None and now - cached[0] < self.prefix_gossip_s:
            return cached[1]
        s = self.prefix_index.summary(self.prefix_gossip_blocks)
        self._gossip_cache = (now, s)
        return s

    # ------------------------------------------------------------------
    # Disaggregated prefill/decode (engine side; wire format and broker
    # in engine/disagg/)

    def kv_bytes_per_token(self, kind: str | None = None) -> int:
        """Bytes of KV cache one token position occupies (k + v payloads
        plus scale planes when int8-quantized) — sizes handoff frames
        and the decode tier's adoption-budget floor. `kind` (a model with
        window and full attention layers alone): "full" or "window", the
        bytes a position takes in that kind's leaves — a full layer's for
        as long as the slot lives, a window layer's for the window's span;
        None: both."""
        c = self.config
        if getattr(c, "window_kind", None) is not None:
            kinds = {"full": (c.attention_kind,),
                     "window": (c.window_kind,)}.get(kind, c.attention_kinds)
            # (a multi-token-prediction module's rows are one more full
            # layer's, written whenever the module drafts)
            per_plane = c.num_kv_heads * (
                sum(len(c.layers_of(name)) for name in kinds)
                + (c.mtp_layers if kind != "window" else 0))
            return 2 * per_plane * (
                c.dim_per_head + 4 if self.kv_quant else
                c.dim_per_head * jnp.dtype(self.cache_dtype).itemsize)
        latent = getattr(c, "latent", None)
        if latent is not None:
            # one row a layer and position, as it lies on the chip: in
            # whole lane tiles (576 values in 640 lanes)
            return (c.num_layers * latent.lanes
                    * jnp.dtype(self.cache_dtype).itemsize)
        # a model with recurrent layers keeps K/V for its attention layers
        # alone (state_bytes_per_slot has the rest of a slot)
        n_layers = (len(c.layers_of(c.attention_kind)) if self._has_state
                    else c.num_layers)
        per_plane = n_layers * c.num_kv_heads
        # sparse attention: one index key a layer and position, unquantized
        index = self.index_bytes_per_token()
        if self.kv_quant:
            # int8 payload + one f32 scale per (layer, head, position)
            return 2 * per_plane * (c.dim_per_head + 4) + index
        return 2 * per_plane * c.dim_per_head * jnp.dtype(
            self.cache_dtype).itemsize + index

    def index_bytes_per_token(self) -> int:
        """Bytes of index keys one token position occupies (learned sparse
        attention; 0 for every other model)."""
        if self._sparse is None:
            return 0
        return (self.config.num_layers * self._sparse.index_head_dim
                * jnp.dtype(self.cache_dtype).itemsize)

    def state_bytes_per_slot(self) -> int:
        """Bytes a slot holds that are not rows per position: the
        recurrent layers' state and convolution tails; 0 for a model
        without such layers."""
        if not self._has_state:
            return 0
        from symmetry_tpu.models.hybrid import state_bytes_per_slot

        return sum(state_bytes_per_slot(self.config,
                                        self.cache_dtype).values())

    def ssm_report(self) -> dict | None:
        """`startup.ssm`: the state kept, each program's form; or None."""
        if not self._has_state:
            return None
        from symmetry_tpu.models import gdn, mamba2, sconv
        from symmetry_tpu.models.hybrid import state_bytes_per_slot

        c = self.config
        cache = self.state.cache
        per_slot = state_bytes_per_slot(c, self.cache_dtype)
        shared = {
            "attention_layers": len(c.layers_of(c.attention_kind)),
            "state_bytes": sum(per_slot.values()) * self.max_slots,
            "prefill_rows_max": self._state_rows_max(),
            "scratch_rows_max": 2 * self._state_rows_max(),
        }
        if c.recurrent_kind == "conv":
            # the tail IS the state: `state_*` count it, no `ssm` leaf
            return {
                "kind": "short_conv", "layers": len(c.layers_of("conv")),
                "taps": c.conv_L_cache, **shared,
                "state_bytes_per_slot": per_slot["conv"],
                "state_dtype": str(cache.conv.dtype),
                "prefill": {"form": "whole prompt, rows stop at their "
                                    "lengths"},
                "decode": sconv.step_form(c),
            }
        if c.recurrent_kind == "mamba":
            kind = {"kind": "mamba2",
                    "mamba_layers": len(c.layers_of("mamba"))}
            if c.mamba_n_groups > 1:
                # groups of B and C the heads read (the norm's groups too)
                kind["groups"] = c.mamba_n_groups
                kind["heads_per_group"] = c.mamba_n_heads // c.mamba_n_groups
            chunk, decode = c.mamba_chunk_size, mamba2.step_form(
                c, cache.ssm.dtype.itemsize)
        else:
            kind = {"kind": "gated_deltanet",
                    "linear_attention_layers":
                        len(c.layers_of("linear_attention"))}
            chunk, decode = c.linear_chunk_size, gdn.step_form(
                c, cache.ssm.dtype.itemsize)
        return {
            **kind, **shared,
            "state_bytes_per_slot": per_slot["ssm"],
            "conv_bytes_per_slot": per_slot["conv"],
            "state_dtype": str(cache.ssm.dtype),
            "conv_dtype": str(cache.conv.dtype),
            "prefill": {"form": "chunked (jnp)", "chunk": chunk},
            # one form serves every state layer: the stack is one operand
            "decode": decode,
        }

    def extract_slot_kv(self, slot: int, p: int):
        """Batch-1 snapshot of decode-lane `slot`'s KV, lengths pinned to
        `p` — the device half of a prefill-tier handoff. Every admission
        path (full prefill, chunked, prefix-cache hit) ends by inserting
        the prompt's KV into the slot lane, so extracting FROM the lane
        is uniform across all of them. Reuses the prefix-cache row
        extract (the decode state's cache is a KVCache with batch on dim
        1), then trims the position axis to the smallest prefill bucket
        holding `p` — the host→device→host transfer the caller pays must
        scale with the prompt, not max_seq_len (the trim is an eager
        slice, one cached variant per bucket; prefill-role warmup covers
        them). The caller np.asarray-syncs the result before the lane
        can be reused (the handoff sink runs on the engine thread, ahead
        of any next admission)."""
        if not 0 <= slot < self.max_slots:
            raise EngineError(f"extract_slot_kv: slot {slot} out of range")
        if self._window is not None:
            raise EngineError(
                "extract_slot_kv: the handoff row carries one K and one V "
                "plane at one capacity and has no place for a window "
                "layer's ring")
        row = self._extract_prefix_row(self.state.cache, jnp.int32(slot),
                                       jnp.int32(p))
        cap = self.bucket_for(max(int(p), 1))
        if cap >= self.max_seq_len:
            return row

        def cut(arr, axis):
            return (jax.lax.slice_in_dim(arr, 0, cap, axis=axis)
                    if arr is not None else None)

        return row._replace(k=cut(row.k, 2), v=cut(row.v, 2),
                            k_scale=cut(row.k_scale, 3),
                            v_scale=cut(row.v_scale, 3))

    def adopt_prefix(self, handoff) -> bool:
        """Decode-tier adoption: a deserialized KV handoff (engine/
        disagg/frames.py KVHandoff, block-manifest format) lands in the
        radix tree, so the migrated request admits through the ordinary
        cached path — ONE block gather + ONE suffix dispatch, the same
        programs a local prefix hit uses.

        The frame carries per-block payloads plus a digest manifest;
        blocks the sender skipped (already shipped once) OR that this
        tree already holds adopt BY REFERENCE — only genuinely new
        blocks are assembled into one bucket-padded row and scattered
        into the pool in a single dispatch. The adopted prefix is the
        longest leading run of resident-or-shipped blocks (a skipped
        block this tier has since evicted just shortens the run — the
        request re-prefills a longer suffix, always causally sound).

        Returns True when a non-empty prefix is (or already was)
        resident, False when nothing could be adopted (routing-only
        frame, pool rejection) — the request then admits through a full
        prefill, which is slower but still token-identical for greedy.
        Structural mismatches between the frame and THIS engine's
        model/cache geometry raise: adopting wrong-shaped or
        wrong-dtype KV would stream garbage."""
        if self.prefix_index is None:
            raise EngineError("adopt_prefix requires the prefix cache "
                              "(role: decode builds it by contract)")
        p = int(handoff.p)
        if p <= 0:
            return False  # routing-only handoff: nothing to adopt
        PB = self.prefix_block
        bs = int(handoff.block_size)
        if p % bs:
            raise EngineError(f"handoff prefix length {p} is not a "
                              f"multiple of its block size {bs}")
        if bool(handoff.kv_quant) != bool(self.kv_quant):
            raise EngineError(
                f"handoff KV quantization ({handoff.kv_quant}) disagrees "
                f"with this engine ({self.kv_quant}) — tiers must share "
                f"the cache layout")
        c = self.config
        want = (c.num_layers, 1, bs, *kv_row(c))
        want_dtype = np.dtype(np.int8 if self.kv_quant
                              else self.cache_dtype)
        for j, planes in handoff.blocks.items():
            k, v = planes["k"], planes["v"]
            if k.shape != want or v.shape != want:
                raise EngineError(
                    f"handoff block {j} KV shape {k.shape} does not "
                    f"match this model ({want})")
            if k.dtype != want_dtype or v.dtype != want_dtype:
                raise EngineError(
                    f"handoff block {j} KV dtype {k.dtype} does not "
                    f"match this engine's cache dtype {want_dtype}")
        tokens = tuple(int(t) for t in handoff.tokens[:p])
        # Leading coverage: resident tree blocks first, then contiguous
        # shipped frame blocks. A hole (skipped-and-evicted) ends it.
        cov = self.prefix_index.match_len(tokens)
        for j in range(p // bs):
            lo, hi = j * bs, (j + 1) * bs
            if hi <= cov:
                continue
            if lo > cov or j not in handoff.blocks:
                break
            cov = hi
        p_eff = PB * (min(cov, p) // PB)
        if p_eff <= 0:
            return False
        plan = self.prefix_index.plan_insert(tokens[:p_eff])
        if plan is None:
            # Fully resident (adoption by reference — the sender skipped
            # everything and this tree still holds it), or the pool
            # rejected the tail even after eviction.
            return self.prefix_index.match_len(tokens[:p_eff]) >= p_eff
        # Assemble the new tail into one bucket-padded batch-1 row and
        # scatter it in ONE dispatch — the same per-bucket program the
        # local store path compiled, so adoption never triggers a
        # mid-traffic XLA compile. The whole assembly runs inside the
        # try: a failure anywhere between plan and commit (no bucket
        # fits, a frame missing its scale planes, a device transfer
        # error) must abort the plan, or its pinned matched prefix and
        # allocated blocks leak forever.
        try:
            capacity = self.bucket_for(p_eff)
            m = plan.matched_len
            k_row = np.zeros((c.num_layers, 1, capacity, *kv_row(c)),
                             want_dtype)
            v_row = np.zeros_like(k_row)
            ks_row = vs_row = None
            if self.kv_quant:
                ks_row = np.zeros(
                    (c.num_layers, 1, c.num_kv_heads, capacity),
                    np.float32)
                vs_row = np.zeros_like(ks_row)
            for j, planes in handoff.blocks.items():
                lo, hi = j * bs, (j + 1) * bs
                if hi <= m or lo >= p_eff:
                    continue  # resident already, or past the adopted run
                # A frame block may straddle p_eff when the sender's
                # block size is not a multiple of this pool's (the
                # floored tail): clip to the adopted run — the row is
                # only capacity wide.
                w = min(hi, p_eff) - lo
                k_row[:, :, lo:lo + w] = planes["k"][:, :, :w]
                v_row[:, :, lo:lo + w] = planes["v"][:, :, :w]
                if self.kv_quant:
                    ks_row[:, :, :, lo:lo + w] = \
                        planes["k_scale"][:, :, :, :w]
                    vs_row[:, :, :, lo:lo + w] = \
                        planes["v_scale"][:, :, :, :w]
            row = KVCache(
                k=jnp.asarray(k_row), v=jnp.asarray(v_row),
                lengths=jnp.full((1,), p_eff, jnp.int32),
                k_scale=jnp.asarray(ks_row) if self.kv_quant else None,
                v_scale=jnp.asarray(vs_row) if self.kv_quant else None,
            )
            self._pool_kv = self._write_blocks(
                self._pool_kv, row,
                self._bucket_ids(capacity, plan.new_ids, at=m // PB))
        except Exception:
            plan.abort()
            raise
        plan.commit()
        return True

    # ------------------------------------------------------------------
    # Chunked prefill (long prompts, interleaved with decode blocks)

    def wants_chunked(self, prompt_len: int) -> bool:
        """True when this prompt should prefill chunk-by-chunk: more than
        one chunk long (a single-chunk prompt IS one dispatch already)."""
        return (self.prefill_chunk is not None
                and prompt_len > self.prefill_chunk)

    def start_chunked_prefill(self, slot: int, prompt_ids: list[int],
                              sampling: SamplingParams,
                              hit: RadixHit | None = None) -> ChunkedPrefill:
        """Begin a chunked prefill for `slot`; drive it to completion with
        advance_chunked_prefill (one device dispatch per call). With a
        prefix-cache `hit`, the cache is seeded from the cached entry and
        the chunk loop covers only the uncached suffix (the long-suffix
        hit path — suffixes <= prefix_align go through
        prefill_and_insert_cached in one dispatch instead). The hit is
        released here in all paths."""
        try:
            if not prompt_ids:
                raise EngineError("empty prompt")
            C = self.prefill_chunk
            assert C is not None
            true_len = len(prompt_ids)
            bucket = self.bucket_for(true_len)  # validates length; cache size
            start = 0
            if hit is not None:
                start = hit.length
                if not 0 < start < true_len:
                    raise EngineError("cached prefix does not fit prompt")
                if tuple(prompt_ids[:start]) != hit.tokens:
                    raise EngineError("prompt diverges from cached prefix")
            sfx_len = true_len - start
            n_chunks = -(-sfx_len // C)
            padded = np.zeros((1, n_chunks * C), np.int32)
            padded[0, :sfx_len] = prompt_ids[start:]

            pk, dk = self._request_keys(sampling)

            cache = self._new_prefix_cache(bucket)
            if hit is not None:
                cache = self._insert_from_blocks(
                    cache, self._pool_kv,
                    self._bucket_ids(bucket, hit.blocks), jnp.int32(start))
                hit.release()  # gather dispatched; blocks free to evict
                self.prefix_index.note_reuse(1, start)
            elif self.prefix_index is not None:
                self.prefix_index.note_miss(1)  # admitted uncached
            return ChunkedPrefill(
                slot=slot, ids=padded, true_len=true_len, n_chunks=n_chunks,
                cache=cache,
                temp=jnp.asarray([sampling.temperature], jnp.float32),
                top_p=jnp.asarray([sampling.top_p], jnp.float32),
                top_k=jnp.asarray([sampling.top_k], jnp.int32),
                prefill_key=pk[None], decode_key=dk[None],
                start_pos=start, full_ids=tuple(prompt_ids),
            )
        finally:
            if hit is not None:
                hit.release()

    def advance_chunked_prefill(self, job: ChunkedPrefill) -> int | None:
        """Run ONE chunk; returns the first sampled token when the prompt
        is complete (the slot is then live; waits for the device), else
        None."""
        toks = self.advance_chunked_prefill_dispatch(job)
        return None if toks is None else int(np.asarray(toks)[0])

    def advance_chunked_prefill_dispatch(self, job: ChunkedPrefill
                                         ) -> jax.Array | None:
        """Dispatch ONE chunk. The final chunk also dispatches the insert
        and returns the first sampled token [1] on the device, unread;
        any other returns None. Chunk offsets are relative to the SUFFIX
        the job carries — with a seeded start_pos the cache lengths
        already position the writes past the prefix."""
        C = self.prefill_chunk
        c0 = job.done_chunks * C
        chunk = jnp.asarray(job.ids[:, c0:c0 + C])
        valid = jnp.asarray([min(C, job.suffix_len - c0)], jnp.int32)
        last = job.done_chunks == job.n_chunks - 1
        if not last:
            job.cache = self._chunk_step(self.params, chunk, job.cache,
                                         valid)
            job.done_chunks += 1
            return None
        last_idx = jnp.asarray([job.suffix_len - 1 - c0], jnp.int32)
        toks, cache = self._chunk_final(
            self.params, chunk, job.cache, valid, last_idx,
            job.temp, job.top_p, job.top_k, job.prefill_key)
        job.done_chunks += 1
        job.cache = None  # old buffer was donated to chunk_final; poison reuse
        # same (batch=1, bucket) insert program the prefill warmup grid
        # compiled — no chunk-specific insert compile
        self._insert(cache, np.asarray([job.slot], np.int32),
                     jnp.asarray([job.true_len], jnp.int32), toks,
                     job.temp, job.top_p, job.top_k, job.decode_key)
        # The finished buffer holds the FULL prompt's KV — scatter its
        # unresident whole blocks into the pool before it is dropped.
        # Completed chunked prefills are exactly the long shared
        # preambles worth caching, and only the NEW tail is written:
        # blocks the tree already holds (e.g. the seed prefix of a
        # seeded job) stay shared by reference.
        if self.prefix_index is not None and job.full_ids:
            PB = self.prefix_block
            p = PB * (job.true_len // PB)
            plan = (self.prefix_index.plan_insert(job.full_ids[:p])
                    if p >= PB else None)
            if plan is not None:
                try:
                    bucket = cache.k.shape[2]
                    self._pool_kv = self._write_blocks(
                        self._pool_kv, cache,
                        self._bucket_ids(bucket, plan.new_ids,
                                         at=plan.matched_len // PB))
                except Exception:
                    plan.abort()
                    raise
                plan.commit()
        return toks

    def _new_prefix_cache(self, capacity: int, batch: int = 1):
        """Fresh batch-N prefix cache, created sharded-in-place (jit with
        out_shardings) so multi-process meshes work like _init_state."""
        c = self.config

        def make():
            return init_cache(c, batch, capacity, self.cache_dtype,
                              quantized=self.kv_quant,
                              count_experts=self._count_experts)

        if self.mesh is not None:
            return jax.jit(make, out_shardings=self._prefix_shard)()
        return jax.jit(make)()

    def _prefill_scratch_for(self, batch: int, bucket: int):
        """The persistent prefix buffer for this (batch, bucket) prefill
        shape — donated through each prefill dispatch and stored back, so
        a shape in active use performs no HBM allocation (see `prefill`
        in _build_jits)."""
        key = (batch, bucket)
        scratch = self._prefill_scratch.pop(key, None)
        if scratch is None:
            # (a block-diffusion admission commits the opening block
            # behind the prompt's bucket)
            room = 0 if self._diffusion is None else self._diffusion.block
            scratch = self._new_prefix_cache(bucket + room, batch)
        return scratch

    def _store_prefill_scratch(self, batch: int, bucket: int,
                               prefix) -> None:
        """Return a prefix buffer to the pool, LRU-bounded: retaining
        EVERY (batch, bucket) grid shape would pin ~5x the token budget
        in KV lanes permanently (~630 MB for the default three-bucket
        llama3-8b grid) — worse steady-state pressure than the per-
        dispatch churn the pool exists to remove. The cap keeps the
        shapes actually in use warm (a serving workload concentrates on
        one or two) and lets rare shapes churn their small buffers."""
        key = (batch, bucket)
        self._prefill_scratch.pop(key, None)
        self._prefill_scratch[key] = prefix  # most-recently-used last
        cap = 2 * max(self.prefill_token_budget,
                      batch * bucket)
        total = sum(b * bk for (b, bk) in self._prefill_scratch)
        for old_key in list(self._prefill_scratch):
            if total <= cap or old_key == key:
                continue
            self._prefill_scratch.pop(old_key)  # dropped ref frees HBM
            total -= old_key[0] * old_key[1]
        if self._has_state:
            # Every ROW of a buffer carries a whole recurrent state
            # whatever its bucket, so tokens do not bound the pool's
            # bytes: rows do — twice the widest batch (_state_rows_max),
            # from the shape alone.
            rows = sum(b for (b, _) in self._prefill_scratch)
            for old_key in list(self._prefill_scratch):
                if rows <= max(2 * self._state_rows_max(), batch):
                    break
                if old_key != key:
                    self._prefill_scratch.pop(old_key)
                    rows -= old_key[0]

    def release_slot(self, slot: int) -> None:
        """A finished slot's cache lane is garbage until reuse (insert
        resets it). Nothing is dispatched: the lane is noted, and the
        next decode program parks it — sets its length to 0, where the
        programs leave it (`_park`, `_stay_parked`) — unless an insert
        reuses it first. One chip or a mesh alike; across hosts the
        release is a command of its own (parallel/multihost.py), so every
        process passes the same lanes."""
        self._park[slot] = True

    def _take_park(self) -> np.ndarray:
        park, self._park = self._park, np.zeros_like(self._park)
        return park

    def _dispatch_decode(self):
        """Dispatch one decode block from the current state; the caller
        keeps the state it returns: (state, tokens [K, B], pairs)."""
        return self._decode(self.params, self.state, self._take_park())

    def _insert(self, prefix, slots: np.ndarray, *rows) -> None:
        """Install the rows of a prefilled prefix into decode lanes
        `slots` (every insert goes through here: a lane that is reused
        before the next decode program is no longer one to park)."""
        self._park[np.asarray(slots)] = False
        if self._has_state:
            self.counters["ssm"]["state_installs"] += len(set(
                np.asarray(slots).tolist()))
        self.state = self._insert_all(self.state, prefix,
                                      jnp.asarray(slots), *rows)

    # What a warm-up record books from the compile watch, in this order
    # behind `program`, `batch`, `bucket`, `t0`, `wall_s`.
    WARM_COUNTS = ("trace_s", "lower_s", "backend_s", "retrieval_s",
                   "cache_hits", "cache_misses")

    @contextlib.contextmanager
    def _warm(self, program: str, batch: int | None = None,
              bucket: int | None = None, **what: Any):
        """One record of the warm-up (`self.warmup_programs`): a
        `start.warm` phase that begins on the stamp that ended the record
        before it, with the compile watch's growth inside it booked to
        `program`. Dispatch is asynchronous, so a program's record holds
        its trace, lowering, compile (or cache read) and launch; the
        device's run of it drains in the next `sync` record, which names
        what it waited for (`waits`)."""
        watch = self.compile_watch
        mark = None if watch is None else watch.mark()
        attrs = {"program": program, "batch": batch, "bucket": bucket,
                 **what}
        phase = self.tracer.phase(
            "start.warm", t0=self._warm_t,
            **{k: v for k, v in attrs.items() if v is not None})
        try:
            with phase:
                yield
        finally:
            self._warm_t = phase.t1
            grown = {} if watch is None else watch.since(mark)
            self.warmup_programs.append({
                **attrs, "t0": phase.t0, "wall_s": phase.t1 - phase.t0,
                **{key: grown.get(key, 0) for key in self.WARM_COUNTS}})

    def _warm_prefill(self, batch: int, bucket: int):
        """Warm-up's prefill at (batch, bucket), over garbage: returns
        (tokens, prefix)."""
        toks, prefix = self._prefill(
            self.params, jnp.zeros((batch, bucket), jnp.int32),
            jnp.ones((batch,), jnp.int32),
            jnp.zeros((batch,), jnp.float32),
            jnp.ones((batch,), jnp.float32),
            jnp.zeros((batch,), jnp.int32),
            jax.random.split(jax.random.key(0), batch),
            self._prefill_scratch_for(batch, bucket))
        self._store_prefill_scratch(batch, bucket, prefix)
        return toks, prefix

    def _warm_insert(self, state, prefix, toks, batch: int):
        """Warm-up's insert of such a prefix: slot 0 with true_len 0
        leaves the state semantically untouched."""
        return self._insert_all(
            state, prefix, jnp.zeros((batch,), jnp.int32),
            jnp.zeros((batch,), jnp.int32), toks,
            jnp.zeros((batch,), jnp.float32),
            jnp.ones((batch,), jnp.float32),
            jnp.zeros((batch,), jnp.int32),
            jax.random.split(jax.random.key(0), batch))

    def warmup(self) -> None:
        """Compile every serving program before traffic: decode, and the
        full (PREFILL_BATCHES × prefill_buckets) prefill/insert grid. A
        fresh XLA compile mid-traffic (~30 s on a real chip) would stall
        every active stream — the first coalesced burst must not pay it.
        Call before the first insert — warmup advances device state with
        garbage that is only harmless on an empty cache.

        Every call it makes is inside one `_warm` record, so the records
        tile the warm-up (from `built_at`, where the build's last span
        ended, when this is the warm-up behind `from_tpu_config`) and
        `self.warmup_programs` says which program a second of it, a
        compile or a cache miss belongs to.

        Role gating (two-tier warmup is the structural win of disagg): a
        "prefill" engine never decodes, so the decode block, the
        concurrent decode+prefill peak probe, and the speculative verify
        program are all skipped — its compile set is the prefill grid,
        the chunk programs, the prefix-cache paths, and ONE extract
        variant for the handoff snapshot. "decode"/"unified" compile the
        full set ("decode" has the prefix store on by contract, so the
        adoption seed-copy shapes are always covered)."""
        decode_side = self.role != "prefill"
        self.warmup_programs = []
        prefill_name = self._prefill.__name__
        decode_name = self._decode.__name__
        # The resume RNG fast-forward (scalar key program, one compile
        # covers every resume depth): warm it so the first mid-stream
        # recovery under load never pays a fresh XLA compile.
        with self._warm("rng_resume"):
            self._rng_resume(jax.random.key(0), 0)
        for batch in self.PREFILL_BATCHES:  # the group-key program
            with self._warm("derive_keys", batch):
                self._derive_keys(
                    self._base_key, np.zeros((batch,), np.int32),
                    np.zeros((batch,), np.int32), np.zeros((batch,), bool))
        if decode_side:
            with self._warm(decode_name):
                self.state, _, _ = self._dispatch_decode()
        for bucket in self.prefill_buckets:
            for batch in self.prefill_batches_for(bucket):
                if batch > self.max_slots:
                    continue
                with self._warm(prefill_name, batch, bucket):
                    toks, prefix = self._warm_prefill(batch, bucket)
                # insert_all compiles per (batch, bucket) too
                with self._warm("insert_all", batch, bucket):
                    self.state = self._warm_insert(self.state, prefix,
                                                   toks, batch)
        # Exercise the CONCURRENT decode+prefill peak once PER BUCKET:
        # serving overlaps an in-flight decode block with a prefill
        # dispatch, and their workspaces coexist in HBM — a configuration
        # that fits each program alone can still OOM at first traffic
        # (observed on a ~95%-full chip: warmup green, first burst
        # prefill RESOURCE_EXHAUSTED 3 s later). Every bucket's widest
        # batch is probed because the peak transient lives at the LARGE
        # buckets (round-2's OOM was batch 4 × 2048, not 16 × 128).
        # Failing HERE turns a mid-traffic wedge into a clean startup
        # failure the caller can react to. Side benefit, measured: the
        # overlapped-execution path is warmed, so in-serving admission
        # dispatches stop paying a first-overlap cost (admit p99 2.5 s →
        # 0.4 s, burst ramp 5.9 s → 4.3 s).
        for bucket in (self.prefill_buckets if decode_side else ()):
            widest = max(b for b in self.prefill_batches_for(bucket)
                         if b <= self.max_slots)
            with self._warm("peak." + decode_name, bucket=bucket):
                pending = self._dispatch_decode()
                self.state = pending[0]
            with self._warm("peak." + prefill_name, widest, bucket):
                toks, _ = self._warm_prefill(widest, bucket)
            # Sync on the PREFILL output: the device queue is FIFO, so
            # its completion implies the decode's too — and JAX surfaces
            # async failures only on the poisoned output, so syncing the
            # decode alone would let a prefill OOM stay pending until
            # first traffic. The first of these syncs also drains every
            # run the grid above dispatched.
            with self._warm("sync", widest, bucket,
                      waits="peak." + prefill_name):
                np.asarray(toks)

        # Chunked-prefill programs: one (step, final) pair per bucket that
        # can hold a multi-chunk prompt. A mid-traffic compile would be the
        # exact stall chunking exists to prevent.
        C = self.prefill_chunk
        if C is not None:
            one = jnp.ones((1,), jnp.int32)
            for bucket in self.prefill_buckets:
                if bucket <= C:
                    continue
                with self._warm("chunk_step", 1, bucket):
                    cache = self._new_prefix_cache(bucket)
                    cache = self._chunk_step(
                        self.params, jnp.zeros((1, C), jnp.int32), cache,
                        one)
                with self._warm("chunk_final", 1, bucket):
                    toks, cache = self._chunk_final(
                        self.params, jnp.zeros((1, C), jnp.int32), cache,
                        one, jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1,), jnp.float32),
                        jnp.ones((1,), jnp.float32),
                        jnp.zeros((1,), jnp.int32),
                        jax.random.split(jax.random.key(0), 1))
                # batch-1 insert at this bucket already compiled above

        # Speculative verify program (only when the knob is on — off keeps
        # warmup's compile set byte-identical): exactly ONE extra compile,
        # the fixed [B, 1+k_draft] verify shape. Zero drafts advance every
        # lane one garbage token — harmless on the pre-insert empty cache,
        # same contract as the decode warmup above. The sync surfaces a
        # marginal-HBM failure at startup.
        if self.spec is not None and not self._mtp and decode_side:
            self._warm_verify()

        if self.role == "prefill":
            # The handoff snapshot programs: the decode-state cache IS a
            # KVCache (batch on dim 1), so the prefix-cache row extract
            # serves as the slot-lane extract — one compiled variant —
            # plus one eager bucket-trim slice per prefill bucket. The
            # final sync doubles as the prefill-role startup-OOM probe
            # (the grid loop above dispatches without syncing).
            for bucket in self.prefill_buckets:
                with self._warm("extract_slot_kv", bucket=bucket):
                    row = self.extract_slot_kv(0, min(
                        bucket, self.max_seq_len))
                with self._warm("sync", bucket=bucket,
                                waits="extract_slot_kv"):
                    np.asarray(row.lengths)

        # Prefix-cache hit-path programs (only when the cache is on —
        # budget 0 keeps warmup exactly as before): per bucket the block
        # scatter (store/adopt path), per (batch, bucket) the row
        # extract (store path), the block-gather seed, and the batched
        # suffix continuation at the prefix_align shape. A hit burst
        # mid-traffic must never pay a fresh XLA compile — the exact
        # stall the cache exists to remove. (The old aligned store
        # needed a seed-copy variant per entry CAPACITY on top of the
        # grid; pool blocks are all one shape, so that whole compile
        # dimension is gone.)
        if self.prefix_index is not None:
            A = self.prefix_align
            for bucket in self.prefill_buckets:
                # All lanes at the trash block: the scatter compiles and
                # runs, and the garbage lands where nobody reads.
                with self._warm("prefix.write_blocks", bucket=bucket):
                    row = self._new_prefix_cache(bucket)
                    self._pool_kv = self._write_blocks(
                        self._pool_kv, row, self._bucket_ids(bucket))
            for bucket in self.prefill_buckets:
                for batch in self.prefill_batches_for(bucket):
                    with self._warm("prefix.extract_row", batch, bucket):
                        scratch = self._prefill_scratch_for(batch, bucket)
                        self._extract_prefix_row(scratch, jnp.int32(0),
                                                 jnp.int32(0))
                    with self._warm("prefix.insert_from_blocks", batch,
                                    bucket):
                        scratch = self._insert_from_blocks(
                            scratch, self._pool_kv,
                            self._bucket_ids(bucket), jnp.int32(0))
                    with self._warm("prefix.suffix", batch, bucket):
                        toks, prefix = self._chunk_final(
                            self.params, jnp.zeros((batch, A), jnp.int32),
                            scratch, jnp.ones((batch,), jnp.int32),
                            jnp.zeros((batch,), jnp.int32),
                            jnp.zeros((batch,), jnp.float32),
                            jnp.ones((batch,), jnp.float32),
                            jnp.zeros((batch,), jnp.int32),
                            jax.random.split(jax.random.key(0), batch))
                        self._store_prefill_scratch(batch, bucket, prefix)
                    # Sync so a marginal-HBM failure surfaces at startup,
                    # not at the first hit burst (same rationale as the
                    # concurrent-peak probe above).
                    with self._warm("sync", batch, bucket,
                                    waits="prefix.suffix"):
                        np.asarray(toks)

        # Dispatch-cache closure. Donation aliases output buffers to the
        # donated inputs, so a state array's PHYSICAL provenance (which
        # executable originally materialized its buffer) survives across
        # program boundaries — and jaxlib's C++ fastpath keys on it. A
        # state that flowed insert→decode→insert therefore dispatches
        # under a different cache key than warmup's init→insert chain,
        # even though every aval, sharding, and layout compares equal:
        # the first serving burst grows _cache_size() without tracing or
        # compiling anything. compile_cache_sizes() is the steady-state
        # recompile tripwire (tests assert it stays flat under traffic),
        # so warmup must populate those signature classes too: run real
        # serving-shaped rounds — back-to-back inserts, decode-interleaved
        # inserts, consecutive decodes — until the per-program variant
        # counts reach a fixed point. The provenance-class graph is finite
        # (one class per materializing executable), so this converges in
        # a couple of rounds; every dispatch hits an already-compiled
        # program, so the cost is a handful of device launches, not
        # compiles. One record a round (`settle`): its seconds are
        # launches, and waits on the device's queue where that is full.
        if decode_side:
            def _settle_insert(state, batch: int, bucket: int):
                toks, prefix = self._warm_prefill(batch, bucket)
                return self._warm_insert(state, prefix, toks, batch)

            for settle_round in range(6):
                with self._warm("settle", round=settle_round):
                    sizes = self.compile_cache_sizes()
                    for bucket in self.prefill_buckets:
                        for batch in self.prefill_batches_for(bucket):
                            if batch > self.max_slots:
                                continue
                            # burst admission: inserts back-to-back
                            self.state = _settle_insert(self.state, batch,
                                                        bucket)
                            # steady decode between admissions
                            self.state, _, _ = self._dispatch_decode()
                            self.state = _settle_insert(self.state, batch,
                                                        bucket)
                        # consecutive decode blocks (no admission between)
                        self.state, _, _ = self._dispatch_decode()
                        self.state, _, _ = self._dispatch_decode()
                    if self.spec is not None and not self._mtp:
                        self.verify_step(
                            np.zeros((self.max_slots, self.spec.k_draft),
                                     np.int32),
                            np.zeros((self.max_slots,), np.int32))
                    settled = self.compile_cache_sizes() == sizes
                if settled:
                    break
        self._warm_t = None  # a later warm-up begins on its own clock read

    def warmup_report(self) -> dict:
        """The warm-up record's totals and its five slowest records: the
        `startup.warmup` block of READY and of every stats reply (the whole
        list, `warmup_programs`, rides READY and the host's log only).
        `compile_s` is trace + lowering + compile-or-fetch seconds,
        `retrieval_s` the part of it spent reading the persistent cache,
        `run_s` the seconds inside `sync` records: the device's runs."""
        records = self.warmup_programs

        def total(*keys: str) -> float:
            return sum(r[key] for r in records for key in keys)

        return {
            "programs": len(records),
            "wall_s": total("wall_s"),
            "compile_s": total("trace_s", "lower_s", "backend_s"),
            "retrieval_s": total("retrieval_s"),
            "run_s": sum(r["wall_s"] for r in records
                         if r["program"] == "sync"),
            "cache_hits": int(total("cache_hits")),
            "cache_misses": int(total("cache_misses")),
            "slowest": sorted(records, key=lambda r: -r["wall_s"])[:5]}

    def _warm_verify(self) -> None:
        name = self._verify.__name__
        with self._warm(name):
            out = self.verify_step_dispatch(
                np.zeros((self.max_slots, self.spec.k_draft), np.int32),
                np.zeros((self.max_slots,), np.int32))
        with self._warm("sync", waits=name):
            for array in out:
                np.asarray(array)

    def verify_step_dispatch(self, draft: np.ndarray, n_draft: np.ndarray
                             ) -> tuple[jax.Array, jax.Array]:
        """Dispatch ONE speculative verify WITHOUT syncing: `draft`
        [B, k_draft] holds each slot's proposed continuation tokens,
        `n_draft` [B] how many are real (0 = no proposal; the slot
        advances one plain token). Returns (tokens [1+k, B], n_emit [B])
        as device futures — the scheduler parks them in its pipeline and
        syncs them an iteration later, so admission/emit host work
        overlaps the verify's device execution exactly like a plain
        decode block (pre-pipeline, the same-iteration sync ate the
        overlap). The next PROPOSAL still waits for the sync: drafts are
        built from this dispatch's output."""
        if self.spec is None or self._mtp:
            raise EngineError("speculative decoding by a host drafter is "
                              "not enabled")
        k = self.spec.k_draft
        if draft.shape != (self.max_slots, k):
            raise EngineError(
                f"draft shape {draft.shape} != {(self.max_slots, k)}")
        self.state, toks, n_emit = self._verify(
            self.params, self.state, jnp.asarray(draft, jnp.int32),
            jnp.asarray(n_draft, jnp.int32), self._take_park())
        return toks, n_emit

    def verify_step(self, draft: np.ndarray, n_draft: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Synchronous verify: dispatch + host transfer in one call
        (tests and non-pipelined callers). tokens[:n, b] with
        n = n_emit[b] are slot b's emitted run for this dispatch."""
        toks, n_emit = self.verify_step_dispatch(draft, n_draft)
        return np.asarray(toks), np.asarray(n_emit)

    def decode_steps_dispatch(self) -> jax.Array:
        """Dispatch one decode block WITHOUT syncing: returns the [K, B]
        device token array as a future. JAX async dispatch lets the caller
        enqueue block N+1 and only then block on block N's tokens, so the
        host-side work (transfer, detokenize, emit) overlaps block N+1's
        device execution (SURVEY §7 hard-part 3: double-buffered token
        fetch)."""
        self.state, toks, pairs = self._dispatch_decode()
        if self.diffusion is not None:
            blocks = self.decode_block // self._diffusion.block
            self.diffusion["forwards"] += blocks * (self._bd_steps + 1)
            self.diffusion["commit_forwards"] += blocks
        if self._count_experts:
            # Start the [experts] count's copy to the host now: by the
            # time this block's tokens are synced it has arrived, and
            # collect_expert_pairs reads it without touching the device.
            pairs.copy_to_host_async()
            self._pairs_pending.append(pairs)
        return toks

    def decode_steps(self) -> np.ndarray:
        """decode_block tokens for every slot; host gets [K, B] int32."""
        toks = np.asarray(self.decode_steps_dispatch())
        self.collect_expert_pairs()
        return toks

    def collect_expert_pairs(self) -> None:
        """Add the per-expert pair counts of every decode block that has
        finished to `self.expert_pairs`. Call it after a block's tokens
        were synced: its count is an output of the same program and its
        copy to the host began at dispatch, so the read waits for
        nothing."""
        while self._pairs_pending and self._pairs_pending[0].is_ready():
            block = np.asarray(self._pairs_pending.popleft())
            self.expert_pairs = [a + int(b) for a, b in
                                 zip(self.expert_pairs, block)]
            for row, (lo, hi) in self._tails:
                # each row's words where the table lays them
                # (`residents.tail_at`, which `tail_words` sized)
                tail = block[lo:hi]
                counts = self.counters[row.block]
                for name, n in (zip(row.words, tail) if row.decode is None
                                else row.decode(tail).items()):
                    counts[name] += int(n)

    def moe_counts(self) -> dict:
        """`stats.engine.moe`'s counters: valid (token, expert) pairs
        computed since start, in all and per expert, as of the last synced
        decode block. Where this chip holds a SHARE of the experts the
        router scores, `pairs` counts every pair the router made,
        `expert_pairs` is of the HELD experts alone (what this chip
        computed, and what its imbalance is over), `held_pairs` /
        `absent_pairs` split the total, and `expert_hits` counts the held
        experts a valid pair fell on, a layer and a forward: what the
        routed form has to read, an expert's matrices a hit."""
        counts = list(self.expert_pairs)
        out = {"pairs": sum(counts), "expert_pairs": counts}
        held = getattr(self.config, "experts_held", None)
        if held is not None:
            mine = counts[held[0]:held[0] + held[1]]
            out.update(expert_pairs=mine, held_pairs=sum(mine),
                       absent_pairs=sum(counts) - sum(mine),
                       expert_hits=self.counters["moe"]["expert_hits"])
        return out

    def moe_report(self) -> dict | None:
        """`startup.moe`: where the expert weights live and which form
        each program kind's expert FFN takes (models/moe.py); None for a
        dense model. Built once: nothing in it changes after start."""
        c = self.config
        if not getattr(c, "num_experts", 0):
            return None
        if self._moe_report is not None:
            return self._moe_report
        from symmetry_tpu.models.moe import (
            grouped_matmul_form, moe_layout, moe_route, whole_stacks)
        from symmetry_tpu.ops.quant import QuantizedTensor

        layers = self.params["layers"]
        # the stacks the trunk hands `moe_mlp` whole: the hybrid trunk its
        # `ffn` stack always, the homogeneous one what `run_layers` does
        whole = layers.get("ffn") or whole_stacks(layers, self.mesh)
        # (an ungated expert has no gate matrix: its up-projection's stack
        # is the same shape)
        ffn = layers.get("ffn", layers)
        wg = ffn["wg"] if "wg" in ffn else ffn["wu"]
        held = getattr(c, "experts_held", None)

        def route(tokens: int) -> str:
            return moe_route(tokens, c.num_experts, c.num_experts_per_tok,
                             held and held[1])

        # by tokens a forward: decode is one per slot (a block per slot
        # under block diffusion); a prefill is batch x bucket for every
        # shape warm-up compiles
        decode_tokens = self.max_slots * (
            1 if self._diffusion is None else self._diffusion.block)
        admissions = {(b, bucket) for bucket in self.prefill_buckets
                      for b in self.prefill_batches_for(bucket)}
        prefills = sorted({b * bucket for b, bucket in admissions})
        # ... and, under block diffusion, the opening block's forwards of
        # an admission: batch x block (`diffusion.admit_forwards` counts
        # them)
        openings = [] if self._diffusion is None else sorted(
            {b * self._diffusion.block for b, _ in admissions})
        gmm_form = grouped_matmul_form(
            wg, min(decode_tokens, *prefills, *openings)
            * c.num_experts_per_tok, one_device=self.mesh is None)
        if gmm_form["form"] != "ragged_dot":
            # the kernel's weight operand: the layers' stack as it lies
            # (it addresses layer and expert), or one layer's slice of it
            # as a stack of one — which XLA copies out before each call
            gmm_form["operand"] = ("stack of one" if whole is None else
                                   f"layers' stack {list(wg.q.shape)}")
        self._moe_report = {
            "experts": c.num_experts, "top_k": c.num_experts_per_tok,
            "layout": moe_layout(self.mesh, c.intermediate_size),
            "route": {"decode": route(decode_tokens),
                      "prefill": {str(t): route(t) for t in prefills}},
            # what the routed form's matmuls run as — under the kernel a
            # gated layer's gate and up are one call (the row tile of the
            # smallest program's rows: the kernel's own from 64)
            "grouped_matmul": gmm_form,
            "quantized_leaf_route": (
                "expert_stack: int8 [L, X, K, N] stays flat (the packed "
                "W8A16 layout has no expert grid dim) and is the grouped "
                "matmul's operand, scales on the accumulator"
                if isinstance(wg, QuantizedTensor) else "not quantized"),
        }
        if openings:
            self._moe_report["route"]["opening"] = {
                str(t): route(t) for t in openings}
        if getattr(c, "router_score", "softmax") == "sigmoid":
            # lfm2_moe: the router's form, and which layers have experts
            self._moe_report["router"] = {
                "score": "sigmoid", "bias": bool(c.router_bias),
                "norm_topk": True, "scale": float(c.routed_scaling_factor)}
            self._moe_report["dense_layers"] = c.num_dense_layers
            self._moe_report["expert_layers"] = len(
                c.layers_ending_in("moe"))
        if getattr(c, "router_input", "ffn_input") != "ffn_input":
            # smallthinker: what the router reads, and the gated activation
            self._moe_report["router_input"] = c.router_input
            self._moe_report["activation"] = c.hidden_act
        if held is not None:
            # a chip's share: the router scores `experts`, the leaves hold
            # `count` of them from `first`; pairs on the others are dropped
            # before the sort (routed) or masked (mixture), gates as routed
            self._moe_report["held"] = {
                "first": held[0], "count": held[1],
                "routed_over": c.num_experts,
                "absent": "dropped before the sort, gates not renormalised"}
            self._moe_report["layers_without_ffn"] = len(
                c.layers_ending_in("none"))
        if not getattr(c, "gated_ffn", True):
            self._moe_report["expert_form"] = (
                f"ungated: {c.hidden_act}(x W_up) W_down, two matrices")
            self._moe_report["activation"] = c.hidden_act
        if c.shared_intermediate_size:
            gated = "gated" if getattr(c, "gated_ffn", True) else "ungated"
            self._moe_report["shared_expert"] = {
                "width": c.shared_intermediate_size,
                "form": (f"dense {gated} FFN, every token, weight "
                         "sigmoid(x . sgate)"
                         if getattr(c, "shared_expert_gate", False)
                         else f"dense {gated} FFN, every token, weight 1")}
        return self._moe_report

    def decode_step(self) -> np.ndarray:
        """One decode step [B] (requires decode_block == 1; tests/bench)."""
        assert self.decode_block == 1, "decode_step needs decode_block=1"
        return self.decode_steps()[0]

    def slot_length(self, slot: int) -> int:
        return int(self.state.cache.lengths[slot])

    @property
    def slot_capacity(self) -> int:
        return self.max_seq_len

    def attention_paths(self) -> dict[str, str]:
        """The attention implementation the served prefill and decode
        programs took (models/llama.py attention_paths: the routing
        itself, asked with this engine's geometry)."""
        from symmetry_tpu.models.llama import (
            attention_paths, sparse_forms, sparse_select)

        kv_bytes = jnp.dtype(jnp.int8 if self.kv_quant
                             else self.cache_dtype).itemsize
        paths = attention_paths(
            self.config, self.max_seq_len, self.mesh,
            batch=self.max_slots, kv_bytes=kv_bytes,
            # (a ring that carries a draft has more rows than the window)
            **({"ring": self._ring} if self._window is not None else {}))
        if self._mtp:
            # every decode step carries the pending token and the module's
            # draft: two positions a slot, each over the rows up to its own
            paths["verify"] = {
                "positions": 2,
                "full": ("the decode kernel once a position"
                         if paths["full"]["decode"] != "xla"
                         else "gqa_attention over the leaf"),
                "window": "gqa_attention, each ring row masked by the "
                          "position it holds"}
        if self._diffusion is not None:
            # the admission program denoises the opening block over its
            # scratch of bucket + block positions: the same routing, asked
            # with that shape
            block = self._diffusion.block
            routes = {attention_paths(self.config, bucket + block, self.mesh,
                                      batch=1, kv_bytes=kv_bytes)["decode"]
                      for bucket in self.prefill_buckets}
            paths["opening_block"] = "/".join(sorted(routes))
            if "xla" in routes:
                paths["opening_block_why"] = (
                    f"the admission's scratch holds bucket + {block} "
                    f"positions: where that is no multiple of 128, or the "
                    f"head is no lane tile, its forwards over it take "
                    f"gqa_attention by shape")
        if self._sparse is not None:
            # the selection each program's attention runs under, and what
            # the indexer's own cache costs
            per_token = self.index_bytes_per_token()
            paths["sparse"] = {
                "topk": self._sparse.topk,
                "index_heads": self._sparse.index_heads,
                "form": sparse_forms(paths),
                "select": sparse_select(paths, self.max_seq_len,
                                        self.max_slots,
                                        self._sparse.index_head_dim),
                "index_bytes_per_token": per_token,
                "index_cache_bytes": (per_token * self.max_slots
                                      * self.max_seq_len)}
        return paths

    def cache_report(self) -> dict | None:
        """`startup.cache`: what a cached position is, for a model whose
        entry is not K and V a head at one capacity (latent attention; window
        and full attention layers); None for any other."""
        if self._window is not None:
            c = self.config
            full, window = (self.kv_bytes_per_token(kind)
                            for kind in ("full", "window"))
            ring = int(self.state.cache.kw.shape[2])
            return {
                "kind": "window+full",
                "dtype": str(self.state.cache.k.dtype),
                "full": {"layers": len(c.layers_of(c.attention_kind)),
                         **({"module_layers": c.mtp_layers}
                            if c.mtp_layers else {}),
                         "rows": self.max_seq_len,
                         "bytes_per_token": full},
                "window": {"layers": len(c.layers_of(c.window_kind)),
                           "rows": ring, "span": c.sliding_window,
                           "bytes_per_token": window},
                "bytes_per_slot": full * self.max_seq_len + window * ring,
                "cache_bytes": self.max_slots * (full * self.max_seq_len
                                                 + window * ring),
                # what ONE capacity for every attention layer would hold
                "uniform_cache_bytes": (self.max_slots * self.max_seq_len
                                        * (full + window))}
        la = self._latent
        if la is None:
            return None
        item = jnp.dtype(self.cache_dtype).itemsize
        per_token = self.kv_bytes_per_token()
        return {
            "kind": "latent", "rank": la.rank, "rope": la.rope,
            "row": la.row, "lanes": la.lanes,
            "layers": self.config.num_layers,
            "dtype": str(jnp.dtype(self.cache_dtype)),
            "row_bytes": la.row * item,
            # as the chip holds it (the 576 values pad to 640 lanes)
            "bytes_per_token": per_token,
            "cache_bytes": per_token * self.max_slots * self.max_seq_len,
            "expanded_bytes_per_token": (
                self.config.num_layers * self.config.num_heads
                * (la.nope + la.rope + la.v) * item),
            "absorbed_factors": {
                "dtype": str(self.params["layers"]["attn"]["wuk"].dtype),
                "bytes": sum(
                    int(self.params["layers"]["attn"][n].nbytes)
                    for n in ("wuk", "wuv"))}}

    def diffusion_report(self) -> dict | None:
        """`startup.diffusion`: the block, the two generation settings and
        what a dispatch is made of; None for any other model."""
        if self._diffusion is None:
            return None
        block, steps = self._diffusion.block, self._bd_steps
        blocks = self.decode_block // block
        return {
            "block": block, "mask_token_id": self._diffusion.mask_token_id,
            "steps": steps,
            "rule": RULE_STATIC if self._bd_threshold is None
            else RULE_DYNAMIC,
            "threshold": self._bd_threshold,
            "transfer_schedule": list(transfer_schedule(block, steps)),
            "blocks_per_dispatch": blocks,
            "forwards_per_dispatch": blocks * (steps + 1),
            "tokens_per_forward": self.max_slots * block,
            "admission": "the admission program denoises and commits the "
                         "opening block: block - (prompt % block) first "
                         "tokens, lanes block-aligned from then on",
            "programs": {"prefill": "bd_prefill",
                         "decode": "bd_decode_block"}}

    def stats_blocks(self) -> dict[str, dict]:
        """Every block of `stats.engine` that this model brings, by name:
        a row's counters (`self.counters`: `ssm`, `dsa`, `mla`, `swa`), and
        `moe` and `diffusion` with what their reports say beside the
        counts. The scheduler's stats reply takes them whole."""
        out = {name: dict(block) for name, block in self.counters.items()}
        if self.expert_pairs:
            # valid (token, expert) pairs computed since start, per expert
            # and in all, as of the last synced decode block; the form each
            # program kind's expert FFN takes (models/moe.py)
            out["moe"] = {**self.moe_counts(),
                          "route": self.moe_report()["route"]}
        if self.diffusion is not None:
            # the settings, the forwards dispatched and what of their
            # tokens reached a stream (the scheduler counts those), as of
            # the last entry read
            report = self.diffusion_report()
            out["diffusion"] = {
                **{k: report[k] for k in ("block", "steps", "rule",
                                          "threshold")},
                **self.diffusion,
                "opening_block_tokens": dict(
                    self.diffusion["opening_block_tokens"])}
        return out

    def startup_reports(self) -> dict[str, dict]:
        """Every block of `startup` that this model brings, by name: built
        once the programs have compiled and the caches are allocated."""
        reports = {"moe": self.moe_report(), "ssm": self.ssm_report(),
                   "diffusion": self.diffusion_report(),
                   "cache": self.cache_report()}
        return {name: report for name, report in reports.items()
                if report is not None}

    def sampling_route(self) -> dict:
        """How every sampling call of the served programs selects its
        top-`cap` window (ops/sampling.py top_k_route: the routing
        itself, asked with this model's vocabulary)."""
        return top_k_route(self.config.vocab_size)

    def weight_stream_bytes(self) -> int:
        """Bytes of parameter data one decode step must stream from HBM:
        every matmul weight (int8 payload + f32 scales, or dense) is read
        in full each step — the stats reply's `weight_bytes_per_step`,
        beside `decode_step_ms`. The input embedding is excluded unless tied:
        it is gathered (B rows), not contracted; tied models re-read it
        as the LM head. Metadata-only (nbytes), safe from any thread."""
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(self.params))
        if not self.config.tie_embeddings:
            total -= self.params["embed"].nbytes
        return total

    def weight_stream_bytes_per_device(self) -> int:
        """Per-device slice of weight_stream_bytes: each leaf counts its
        LOCAL shard size (sharding.shard_shape), so TP sharded leaves
        divide by the axis size while replicated leaves count in full on
        every device — the actual per-chip HBM stream one decode step
        costs (tests/test_qmm_mesh.py holds it under the aggregate).
        Metadata-only, safe from any thread;
        on a single device this equals weight_stream_bytes."""

        def local_nbytes(leaf) -> int:
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:
                return leaf.nbytes
            shard = sharding.shard_shape(leaf.shape)
            n = leaf.dtype.itemsize
            for d in shard:
                n *= d
            return n

        total = sum(local_nbytes(leaf)
                    for leaf in jax.tree.leaves(self.params))
        if not self.config.tie_embeddings:
            total -= local_nbytes(self.params["embed"])
        return total

    def compile_cache_sizes(self) -> dict[str, int]:
        """Compiled-variant count per jitted primitive. Warmup fills
        these; steady-state serving must never grow them — a mid-traffic
        XLA compile is the stall every warmup path exists to prevent
        (tests assert zero steady-state recompiles against this)."""
        out: dict[str, int] = {}
        for name in ("_prefill", "_decode", "_verify", "_chunk_step",
                     "_chunk_final", "_insert_all", "_insert_from_blocks",
                     "_write_blocks", "_extract_prefix_row",
                     "_derive_keys"):
            fn = getattr(self, name, None)
            if fn is not None and hasattr(fn, "_cache_size"):
                out[name] = fn._cache_size()
        return out

    # ------------------------------------------------------------------

    @classmethod
    def from_tpu_config(cls, tpu_cfg: Any, *, tracer: Tracer | None = None,
                        compile_watch: Any = None,
                        t0: float | None = None) -> "InferenceEngine":
        """Build from a provider.yaml `tpu:` section (provider/config.py).

        With `tpu.multihost` set, joins the jax.distributed job first and
        builds the hybrid DCN×ICI mesh over the GLOBAL device set — every
        process (rank 0 and workers) constructs the engine identically.

        Raises NoChipError (utils/device.py) before any weight is made
        when the devices JAX hands out are not TPUs and the CPU was not
        pinned by name.

        The build is three spans of the start-up timeline on `tracer`,
        the first begun at `t0` (the stamp that ended the caller's span
        before it), each on the stamp that ended the last: `build.devices`
        (the first touch of the backend: the device client's own init),
        `build.params` (the weights made or loaded, quantised, absorbed,
        placed) and `build.state` (the cache, the recurrent state and the
        prefix pool allocated, the jitted callables made). `tracer` and
        `compile_watch` stay with the engine for `warmup()`.
        """
        from symmetry_tpu.utils.device import require_chip

        tracer = tracer if tracer is not None else Tracer()
        span = tracer.phase("start.build.devices", t0=t0, parent=None)
        with span:
            mesh_spec = MeshSpec.from_dict(tpu_cfg.mesh)
            mh = tpu_cfg.multihost
            if mh:
                from symmetry_tpu.parallel.multihost import (
                    build_multihost_mesh, init_distributed)

                init_distributed(mh["coordinator"], mh["num_processes"],
                                 mh.get("process_id", 0))
            require_chip()
            if mh:
                mesh = build_multihost_mesh(mesh_spec,
                                            mh.get("dcn_data", 1))
            else:
                mesh = build_mesh(mesh_spec) if mesh_spec.size > 1 else None
        span = tracer.phase("start.build.params", t0=span.t1, parent=None)
        with span:
            params, config, dtype, tokenizer = cls._params_from_tpu_config(
                tpu_cfg, mesh)
        span = tracer.phase("start.build.state", t0=span.t1, parent=None)
        with span:
            engine = cls(
                config, params, tokenizer, mesh=mesh,
                max_slots=tpu_cfg.max_batch_size,
                max_seq_len=tpu_cfg.max_seq_len,
                prefill_buckets=tpu_cfg.prefill_buckets,
                cache_dtype=dtype,
                decode_block=getattr(tpu_cfg, "decode_block", 1),
                kv_quant=tpu_cfg.kv_quantization == "int8",
                prefill_chunk=getattr(tpu_cfg, "prefill_chunk", 256),
                prefill_token_budget=getattr(tpu_cfg, "prefill_token_budget",
                                             None),
                prefix_cache_bytes=int(
                    (getattr(tpu_cfg, "prefix_cache_mb", None) or 0) * 2**20),
                prefix_block_tokens=int(
                    getattr(tpu_cfg, "prefix_block_tokens", None) or 16),
                prefix_gossip_blocks=int(
                    getattr(tpu_cfg, "prefix_gossip_blocks", None) or 0),
                # is-None, not falsy-or: an explicit 0.0 means "recompute
                # on every heartbeat probe", not the default cadence
                prefix_gossip_s=float(
                    2.0 if getattr(tpu_cfg, "prefix_gossip_s", None) is None
                    else tpu_cfg.prefix_gossip_s),
                speculative=SpecConfig.from_knob(
                    getattr(tpu_cfg, "speculative", None)),
                fused_dequant=bool(getattr(tpu_cfg, "fused_dequant", False)),
                # "disagg" is the BACKEND's role (it spawns a prefill and a
                # decode host, each of which sees its own tier role here);
                # an engine can only be one tier or unified.
                role=getattr(tpu_cfg, "role", "unified") or "unified",
                diffusion_steps=getattr(tpu_cfg, "diffusion_steps", None),
                diffusion_threshold=getattr(tpu_cfg, "diffusion_threshold",
                                            None),
                tracer=tracer, compile_watch=compile_watch,
            )
        engine.built_at = engine._warm_t = span.t1
        return engine

    @staticmethod
    def _params_from_tpu_config(tpu_cfg: Any, mesh: Any):
        """The weights of `from_tpu_config`, on the device: (params,
        config, dtype, tokenizer)."""
        dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                  "float16": jnp.float16}
        if tpu_cfg.dtype not in dtypes:
            raise EngineError(f"unsupported tpu.dtype {tpu_cfg.dtype!r}; "
                              f"expected one of {sorted(dtypes)}")
        dtype = dtypes[tpu_cfg.dtype]

        if tpu_cfg.quantization not in (None, "int8"):
            raise EngineError(
                f"unsupported tpu.quantization {tpu_cfg.quantization!r}")
        if tpu_cfg.kv_quantization not in (None, "int8"):
            raise EngineError(
                f"unsupported tpu.kv_quantization {tpu_cfg.kv_quantization!r}")
        quant = tpu_cfg.quantization == "int8"

        if tpu_cfg.checkpoint_path:
            from symmetry_tpu.engine.weights import (
                load_checkpoint, load_warm_cache, save_warm_cache)
            from symmetry_tpu.utils.logging import logger

            # Warm restart (SURVEY §5.4): the finished tree — stacked,
            # transposed, quantized — is cached beside the checkpoint on
            # first load; restarts mmap it straight to device.
            warm = None
            # Single-process only, for BOTH directions: on a multi-host
            # mesh, a cache present on some hosts but not others would
            # send processes down divergent load paths and hang the first
            # cross-host collective.
            use_warm = (getattr(tpu_cfg, "warm_cache", True)
                        and jax.process_count() == 1)
            if use_warm:
                try:
                    warm = load_warm_cache(
                        tpu_cfg.checkpoint_path, dtype=dtype,
                        quantize=quant, mesh=mesh)
                except Exception as exc:  # noqa: BLE001 — cache is advisory
                    logger.warning(f"warm cache unreadable, cold load: {exc}")
            if warm is not None:
                params, config = warm
                logger.info("weights loaded from warm cache")
            else:
                params, config = load_checkpoint(
                    tpu_cfg.checkpoint_path, mesh=mesh, dtype=dtype)
                if quant:
                    from symmetry_tpu.models.llama import quantize_params

                    params = quantize_params(params)
                params = absorb_latent(params, config, dtype)
                if use_warm:
                    try:
                        save_warm_cache(tpu_cfg.checkpoint_path, params,
                                        config, dtype=dtype, quantize=quant)
                        logger.info("warm weight cache written")
                    except Exception as exc:  # noqa: BLE001
                        logger.warning(f"warm cache not written: {exc}")
        else:
            config = preset(tpu_cfg.model_preset or "tiny")
            if mesh is not None:
                from symmetry_tpu.models.llama import param_logical_axes

                # Initialize directly as global sharded arrays (works when
                # the mesh spans processes; device_put of host values
                # cannot). Quantized leaves init int8 in the same program.
                axes = param_logical_axes(config)
                if quant:
                    from symmetry_tpu.models.llama import (
                        quantized_logical_axes)

                    axes = quantized_logical_axes(axes)
                shardings = shardings_for(axes, mesh)
                params = jax.jit(
                    lambda: init_params(config, jax.random.key(0), dtype,
                                        quantize=quant,
                                        shardings=shardings),
                    out_shardings=shardings)()
            else:
                params = init_params(config, jax.random.key(0), dtype,
                                     quantize=quant)
        # Tokenizer after config resolution: the byte fallback must span
        # the MODEL's vocab or sampled ids stream as silence (tokenizer.py).
        tokenizer = get_tokenizer(tpu_cfg.tokenizer_path,
                                  vocab_size=config.vocab_size)
        return params, config, dtype, tokenizer
