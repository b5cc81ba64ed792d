"""Inference pipeline parallelism: layer stages over the `stage` mesh axis.

SURVEY §2.3's PP row ("optional for serving; layer-stage sharding over DCN
for multi-host pods"): the model's stacked layers shard across pipeline
stages, activations flow stage-to-stage as point-to-point `ppermute`
transfers (no per-layer collectives — the property that makes PP the
DCN-friendly axis), and GPipe-style microbatching keeps every stage busy
once the pipe fills.

Schedule (M microbatches, P stages, static loop of M + P - 1 rounds):

    round t: stage s processes microbatch (t - s) when 0 <= t - s < M,
             then ppermutes its activation to stage s + 1.

Everything is SPMD under `shard_map`: inactive stages compute on garbage
and a `jnp.where` on the round index selects whether their cache/output
writes take effect — no data-dependent control flow, one compiled program.

Cache discipline: the KV cache shards its LAYER dim over `stage` (each
stage owns its layers' KV) and is viewed [L_local, M, Bm, ...] so a round
updates exactly the active microbatch's rows via dynamic slice in/out.
Layer indices inside a stage are local, which is what the local cache
shard expects (models/llama.py run_layers).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from symmetry_tpu.models.llama import KVCache, ModelConfig, run_layers
from symmetry_tpu.ops.norm import rms_norm
from symmetry_tpu.parallel.sharding import DEFAULT_RULES

# Sharding rules for pipeline mode: layers (params AND cache) over `stage`.
PIPELINE_RULES = {**DEFAULT_RULES, "layers": "stage"}


def _mb_slice(arr, m, n_micro):
    """Static-shape microbatch slice along the batch dim (axis 0)."""
    bm = arr.shape[0] // n_micro
    return jax.lax.dynamic_slice_in_dim(arr, m * bm, bm, axis=0)


def _pp_shard_fn(params, tokens, cache: KVCache, seq_lens,
                 *, config: ModelConfig, n_stages: int, n_micro: int,
                 use_flash: bool):
    """Per-stage body. params['layers'] and cache.k/v arrive with the LOCAL
    layer shard (L/P leading dim); everything else replicated."""
    stage = jax.lax.axis_index("stage")
    B, S = tokens.shape
    bm = B // n_micro
    E = params["embed"].shape[1]

    positions = cache.lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    kv_valid = cache.lengths + seq_lens

    # Local cache viewed per-microbatch: [L_loc, M, Bm, T, K, D].
    def split_mb(x, axis=1):
        return x.reshape(x.shape[:axis] + (n_micro, bm) + x.shape[axis + 1:])

    def merge_mb(x, axis=1):
        # inverse of split_mb: collapse the (M, Bm) pair back into B
        return x.reshape(x.shape[:axis] + (n_micro * bm,) + x.shape[axis + 2:])

    kc = split_mb(cache.k)
    vc = split_mb(cache.v)
    ksc = split_mb(cache.k_scale) if cache.quantized else None
    vsc = split_mb(cache.v_scale) if cache.quantized else None

    h_recv = jnp.zeros((bm, S, E), params["embed"].dtype)
    outputs = jnp.zeros((n_micro, bm, S, E), params["embed"].dtype)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def round_body(t, carry):
        h_recv, kc, vc, ksc, vsc, outputs = carry
        m = jnp.clip(t - stage, 0, n_micro - 1)  # my microbatch this round
        active = (stage <= t) & (t - stage < n_micro)

        # Stage 0 sources from the embedding; later stages from the wire.
        toks_m = _mb_slice(tokens, m, n_micro)
        h_in = jnp.where(stage == 0,
                         jnp.take(params["embed"], toks_m, axis=0), h_recv)

        mb_cache = KVCache(
            k=jax.lax.dynamic_index_in_dim(kc, m, 1, keepdims=False),
            v=jax.lax.dynamic_index_in_dim(vc, m, 1, keepdims=False),
            lengths=_mb_slice(cache.lengths, m, n_micro),
            k_scale=(jax.lax.dynamic_index_in_dim(ksc, m, 1, keepdims=False)
                     if ksc is not None else None),
            v_scale=(jax.lax.dynamic_index_in_dim(vsc, m, 1, keepdims=False)
                     if vsc is not None else None),
        )
        h_out, new_mb_cache = run_layers(
            params["layers"], h_in, mb_cache,
            _mb_slice(positions, m, n_micro), _mb_slice(kv_valid, m, n_micro),
            _mb_slice(seq_lens, m, n_micro), config, use_flash=use_flash,
            # Stage-sharded cache under shard_map: keep the XLA scatter
            # path (the fused append kernel is gated to unsharded caches).
            kv_append_ok=False)

        # Inactive rounds ran on garbage: select at MICROBATCH granularity
        # (old slice vs new slice) and do one in-place-able update — a
        # full-array where would stream the whole local cache through HBM
        # every round.
        def put(big, new_small, old_small):
            sel = jnp.where(active, new_small, old_small)
            return jax.lax.dynamic_update_index_in_dim(big, sel, m, 1)

        kc = put(kc, new_mb_cache.k, mb_cache.k)
        vc = put(vc, new_mb_cache.v, mb_cache.v)
        if ksc is not None:
            ksc = put(ksc, new_mb_cache.k_scale, mb_cache.k_scale)
            vsc = put(vsc, new_mb_cache.v_scale, mb_cache.v_scale)

        # The LAST stage's activations are the model output for microbatch m.
        done = active & (stage == n_stages - 1)
        outputs = jnp.where(
            done,
            jax.lax.dynamic_update_index_in_dim(outputs, h_out, m, 0),
            outputs)

        h_next = jax.lax.ppermute(h_out, "stage", perm)
        return h_next, kc, vc, ksc, vsc, outputs

    carry = (h_recv, kc, vc, ksc, vsc, outputs)
    for t in range(n_micro + n_stages - 1):  # static: P+M-1 rounds
        carry = round_body(t, carry)
    _, kc, vc, ksc, vsc, outputs = carry

    # Only the last stage wrote real outputs (zeros elsewhere): the psum
    # replicates them to every stage, satisfying the P() out_spec.
    outputs = jax.lax.psum(outputs, "stage")
    h = outputs.reshape(n_micro * bm, S, E)
    h = rms_norm(h, params["final_norm"], config.rms_eps)
    new_cache = KVCache(
        k=merge_mb(kc), v=merge_mb(vc), lengths=kv_valid,
        k_scale=merge_mb(ksc) if ksc is not None else None,
        v_scale=merge_mb(vsc) if vsc is not None else None,
    )
    return h, new_cache


def pipeline_forward_hidden(
    params: dict,
    config: ModelConfig,
    tokens: jnp.ndarray,      # [B, S] int32
    cache: KVCache,           # layer dim sharded over `stage`
    mesh,
    seq_lens: jnp.ndarray | None = None,
    *,
    n_microbatches: int = 2,
    prefill_flash: bool = False,
) -> tuple[jnp.ndarray, KVCache]:
    """Pipeline-parallel decoder trunk (embed → staged layers → final
    norm). Returns (hidden [B, S, E] on every stage, updated cache).

    Params/cache must be sharded with PIPELINE_RULES (layers → stage).
    The batch must divide n_microbatches; outputs are replicated across
    stages (only the last stage writes real outputs — the psum over
    `stage` at the end of the schedule replicates them everywhere).
    prefill_flash routes each stage's local attention through the Pallas
    flash kernel, under forward_hidden's empty-cache contract.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape["stage"]
    B, S = tokens.shape
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} "
                         f"microbatches")
    if config.num_layers % n_stages:
        raise ValueError(f"{config.num_layers} layers not divisible by "
                         f"{n_stages} stages")
    other = [a for a in ("data", "context", "expert", "model")
             if mesh.shape[a] != 1]
    if other:
        # The in_specs below replicate non-layer dims; composing PP with
        # TP/DP/EP sharding needs those specs carried through — refuse
        # rather than silently all-gathering TP-sharded weights.
        raise ValueError(
            f"pipeline_forward_hidden shards only the stage axis; mesh has "
            f"non-trivial axes {other} — use a stage-only (sub)mesh")
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)
    # Same predicate as forward_hidden: the flash kernel handles sliding
    # windows natively (window-bounded block range).
    use_flash = prefill_flash and S > 1

    layer_spec = P("stage")
    param_specs = {
        "embed": P(), "final_norm": P(),
        "layers": jax.tree.map(lambda _: layer_spec, params["layers"]),
    }
    if "lm_head" in params:
        param_specs["lm_head"] = P()
    cache_specs = KVCache(
        k=layer_spec, v=layer_spec, lengths=P(),
        k_scale=layer_spec if cache.quantized else None,
        v_scale=layer_spec if cache.quantized else None,
    )

    fn = functools.partial(_pp_shard_fn, config=config, n_stages=n_stages,
                           n_micro=n_microbatches, use_flash=use_flash)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(param_specs, P(), cache_specs, P()),
        out_specs=(P(), cache_specs),
        # Pallas calls (flash prefill) inside the body don't carry VMA
        # annotations; output replication is by construction (the psum).
        check_vma=False,
    )(params, tokens, cache, seq_lens)
