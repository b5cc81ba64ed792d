"""Weight loading: HF safetensors → sharded on-device params.

Serving-side "checkpoint/resume" (SURVEY §5.4): the TPU analog of the
reference's nonexistent model state is weight loading, and the hard
constraint is host RAM (SURVEY §7 hard-part 5: llama3-70b must not
materialize on the host). Strategy:

  - `jax.make_array_from_callback` per parameter: XLA asks for exactly the
    index-slice each local device needs, and the callback reads just that
    slice from the memory-mapped safetensors files (`get_slice`). Host
    footprint = one device shard at a time; on multi-host, each host only
    ever touches its own shards.
  - The stacked-layers layout ([L, ...] scanned by the model) is assembled
    slice-wise: a request for layers l0:l1 reads those layers' HF tensors
    only.
  - HF linear weights are [out, in]; ours are [in, out]. Transposition is
    folded into the slice read (swap the requested index, transpose the
    small result), never applied to the full tensor.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from symmetry_tpu.models.llama import (
    HF_EXPERT_MAP,
    HF_LAYER_MAP,
    HF_TOP_MAP,
    ModelConfig,
    config_from_hf,
    hf_expert_name,
    hf_moe_names,
    optional_layer_params,
    init_params,
    param_logical_axes,
)
from symmetry_tpu.parallel.sharding import shardings_for


class CheckpointError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# In-memory conversion (tests, tiny models, torch-exported dicts)


def convert_hf_state_dict(
    tensors: dict[str, np.ndarray], config: ModelConfig
) -> dict:
    """Convert a full in-memory HF llama/mixtral state dict to our pytree
    (a granitemoehybrid, qwen3_next or lfm2_moe one through
    models/hybrid.py's name maps)."""
    if getattr(config, "layer_types", None):
        from symmetry_tpu.models import hybrid

        try:
            return hybrid.convert_hf_state_dict(tensors, config)
        except (KeyError, ValueError) as exc:
            family = {"linear_attention": "qwen3_next", "conv": "lfm2_moe",
                      None: ("deepseek_v3" if config.latent is not None
                             else "smallthinker")}.get(
                config.recurrent_kind, "granitemoehybrid")
            raise CheckpointError(f"{family} checkpoint: {exc}")
    n_exp = getattr(config, "num_experts", 0)
    router_name, experts_module, expert_map = hf_moe_names(config)
    absent = optional_layer_params(config)
    per_layer: dict[str, list] = {
        ours: [None] * config.num_layers
        for ours, _ in HF_LAYER_MAP.values()
        # bias params exist only for attention_bias (qwen2) configs, q/k
        # norms and the indexer only for a config that has them
        if ours not in absent}
    if n_exp:
        # MoE FFN params come per (layer, expert); stack experts inside
        # each layer. The dense FFN names are absent in mixtral files.
        for ours in ("wg", "wu", "wd"):
            per_layer[ours] = [[None] * n_exp
                               for _ in range(config.num_layers)]
        per_layer["router"] = [None] * config.num_layers
    top: dict[str, np.ndarray] = {}
    for name, arr in tensors.items():
        if name in HF_TOP_MAP:
            ours, transpose = HF_TOP_MAP[name]
            top[ours] = arr.T if transpose else arr
        elif name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_str, _, sub = rest.partition(".")
            layer = int(idx_str)
            if n_exp and sub == router_name:
                per_layer["router"][layer] = arr.T
            elif n_exp and sub.startswith(experts_module + "."):
                parts = sub.split(".")       # experts . <e> . w1 . weight
                expert, w = int(parts[2]), parts[3]
                if w not in expert_map:
                    raise CheckpointError(f"unmapped HF tensor {name!r}")
                per_layer[expert_map[w]][layer][expert] = arr.T
            elif sub in HF_LAYER_MAP:
                ours, transpose = HF_LAYER_MAP[sub]
                if ours not in per_layer:
                    raise CheckpointError(
                        f"checkpoint has {name!r} but the config does not "
                        f"enable " + ("attention_bias" if ours[0] == "b"
                                      else f"what {ours!r} belongs to"))
                per_layer[ours][layer] = arr.T if transpose else arr
            else:
                raise CheckpointError(f"unmapped HF tensor {name!r}")
        else:
            raise CheckpointError(f"unmapped HF tensor {name!r}")

    if n_exp:
        for ours in ("wg", "wu", "wd"):
            per_layer[ours] = [np.stack(experts) if all(
                e is not None for e in experts) else None
                for experts in per_layer[ours]]
    for ours, lst in per_layer.items():
        missing = [i for i, a in enumerate(lst) if a is None]
        if missing:
            raise CheckpointError(f"missing layers {missing} for param {ours!r}")

    params: dict = {
        "embed": top["embed"],
        "layers": {ours: np.stack(lst) for ours, lst in per_layer.items()},
        "final_norm": top["final_norm"],
    }
    if not config.tie_embeddings:
        if "lm_head" not in top:
            raise CheckpointError("checkpoint lacks lm_head and config is untied")
        params["lm_head"] = top["lm_head"]
    return params


# ---------------------------------------------------------------------------
# Streaming safetensors loading


class _SafetensorsDir:
    """Index over one or many .safetensors files in an HF checkpoint dir."""

    def __init__(self, path: str) -> None:
        from safetensors import safe_open

        self._open = safe_open
        self._files: dict[str, str] = {}  # tensor name -> file path
        index_path = os.path.join(path, "model.safetensors.index.json")
        if os.path.exists(index_path):
            with open(index_path, "r", encoding="utf-8") as fh:
                index = json.load(fh)
            for name, fname in index["weight_map"].items():
                self._files[name] = os.path.join(path, fname)
        else:
            single = [f for f in sorted(os.listdir(path))
                      if f.endswith(".safetensors")]
            if not single:
                raise CheckpointError(f"no .safetensors files under {path}")
            for fname in single:
                fpath = os.path.join(path, fname)
                with safe_open(fpath, framework="np") as f:
                    for name in f.keys():
                        self._files[name] = fpath
        self._handles: dict[str, Any] = {}

    def names(self) -> Iterator[str]:
        return iter(self._files)

    def _handle(self, name: str):
        fpath = self._files[name]
        if fpath not in self._handles:
            self._handles[fpath] = self._open(fpath, framework="np")
        return self._handles[fpath]

    def read(self, name: str) -> np.ndarray:
        """The whole tensor, in the file's layout."""
        return self._handle(name).get_tensor(name)

    def read_slice(self, name: str, index: tuple[slice, ...],
                   transpose: bool) -> np.ndarray:
        """Read tensor[index] where index refers to the (maybe-transposed)
        logical layout we store; the file read is of the swapped index."""
        if name not in self._files:
            raise CheckpointError(f"tensor {name!r} not in checkpoint")
        sl = self._handle(name).get_slice(name)
        if transpose:
            r, c = index
            return np.ascontiguousarray(sl[c, r].T)
        return sl[index]


def _norm_index(index, ndim: int) -> tuple[slice, ...]:
    """Expand a device index (possibly Ellipsis/short) to one slice per dim."""
    if index is Ellipsis:
        return (slice(None),) * ndim
    index = tuple(index)
    out = []
    for ix in index:
        if ix is Ellipsis:
            out.extend([slice(None)] * (ndim - len(index) + 1))
        else:
            out.append(ix)
    out.extend([slice(None)] * (ndim - len(out)))
    return tuple(out)


def load_checkpoint(
    path: str,
    config: ModelConfig | None = None,
    *,
    mesh=None,
    rules: dict[str, str | None] | None = None,
    dtype=jnp.bfloat16,
) -> tuple[dict, ModelConfig]:
    """Load an HF llama-family checkpoint dir into sharded device arrays.

    Returns (params, config). If `config` is None it is derived from the
    checkpoint's config.json. With no mesh, arrays land unsharded on the
    default device (single-chip path).
    """
    if config is None:
        cfg_path = os.path.join(path, "config.json")
        if not os.path.exists(cfg_path):
            raise CheckpointError(f"no config.json under {path}")
        with open(cfg_path, "r", encoding="utf-8") as fh:
            config = config_from_hf(json.load(fh))

    store = _SafetensorsDir(path)
    names = set(store.names())
    if getattr(config, "layer_types", None):
        # Two mixer kinds, stacked per kind: whole tensors through the
        # in-memory converter, one device (no mesh for such a model yet).
        if mesh is not None:
            raise CheckpointError("a model with recurrent layers loads on "
                                  "one device")
        tensors = {n: store.read(n) for n in sorted(names)}
        params = convert_hf_state_dict(tensors, config)
        abstract = jax.eval_shape(
            lambda: init_params(config, jax.random.key(0), dtype))
        for derived in ("wuk", "wuv"):  # llama.absorb_latent's, of the
            # finished (quantised) tree: no checkpoint holds them
            abstract["layers"].get("attn", {}).pop(derived, None)
        return jax.tree.map(
            lambda a, like: jnp.asarray(a, like.dtype), params,
            abstract), config
    tied = config.tie_embeddings or "lm_head.weight" not in names

    axes = param_logical_axes(config)
    abstract = jax.eval_shape(
        lambda: init_params(config, jax.random.key(0), dtype))
    if tied and "lm_head" in abstract:
        raise CheckpointError("checkpoint ties embeddings but config does not")

    if mesh is not None:
        shardings = shardings_for(axes, mesh, rules)
    else:
        dev = jax.devices()[0]
        shardings = jax.tree.map(lambda _: jax.sharding.SingleDeviceSharding(dev),
                                 abstract)

    inv_layer = {ours: (hf, t) for hf, (ours, t) in HF_LAYER_MAP.items()}
    inv_top = {ours: (hf, t) for hf, (ours, t) in HF_TOP_MAP.items()}

    def top_reader(ours: str) -> Callable:
        hf_name, transpose = inv_top[ours]

        def read(index):
            ndim = len(abstract[ours].shape)
            arr = store.read_slice(hf_name, _norm_index(index, ndim), transpose)
            return arr.astype(dtype)

        return read

    n_exp = getattr(config, "num_experts", 0)
    router_name = hf_moe_names(config)[0]

    def layer_reader(ours: str) -> Callable:
        if n_exp and ours == "router":
            def read(index):
                l_sl, *rest = _norm_index(index, 3)
                layers = range(*l_sl.indices(config.num_layers))
                per = [store.read_slice(
                    f"model.layers.{l}.{router_name}", tuple(rest), True)
                    for l in layers]
                return np.stack(per).astype(dtype)

            return read
        if n_exp and ours in ("wg", "wu", "wd"):
            def read(index):
                # stacked [L, X, in, out]: one HF tensor per (layer, expert)
                l_sl, x_sl, *rest = _norm_index(index, 4)
                layers = range(*l_sl.indices(config.num_layers))
                experts = range(*x_sl.indices(n_exp))
                per = [np.stack([store.read_slice(
                    hf_expert_name(l, e, ours, config), tuple(rest), True)
                    for e in experts]) for l in layers]
                return np.stack(per).astype(dtype)

            return read
        hf_sub, transpose = inv_layer[ours]

        def read(index):
            ndim = len(abstract["layers"][ours].shape)
            l_sl, *rest = _norm_index(index, ndim)
            layers = range(*l_sl.indices(config.num_layers))
            per = [store.read_slice(f"model.layers.{l}.{hf_sub}",
                                    tuple(rest), transpose)
                   for l in layers]
            return np.stack(per).astype(dtype)

        return read

    def materialize(ours_path: tuple, aval, sharding) -> jax.Array:
        if ours_path[0] == "layers":
            read = layer_reader(ours_path[1])
        else:
            read = top_reader(ours_path[0])
        return jax.make_array_from_callback(aval.shape, sharding,
                                            lambda ix: read(ix))

    params = {
        "embed": materialize(("embed",), abstract["embed"], shardings["embed"]),
        "layers": {
            k: materialize(("layers", k), abstract["layers"][k],
                           shardings["layers"][k])
            for k in abstract["layers"]
        },
        "final_norm": materialize(("final_norm",), abstract["final_norm"],
                                  shardings["final_norm"]),
    }
    if "lm_head" in abstract:
        params["lm_head"] = materialize(("lm_head",), abstract["lm_head"],
                                        shardings["lm_head"])
    return params, config


def save_checkpoint(path: str, params: dict, config: ModelConfig) -> None:
    """Write params back out as a single HF-layout safetensors file (tests,
    tiny-model fixtures, re-export of quantized weights)."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    if getattr(config, "layer_types", None):
        from symmetry_tpu.models import hybrid

        c = config
        save_file({k: np.ascontiguousarray(v) for k, v in
                   hybrid.to_hf_state_dict(jax.device_get(params),
                                           c).items()},
                  os.path.join(path, "model.safetensors"))
        with open(os.path.join(path, "config.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(hybrid.hf_config(c), fh, indent=2)
        return
    tensors: dict[str, np.ndarray] = {}
    inv_top = {ours: (hf, t) for hf, (ours, t) in HF_TOP_MAP.items()}
    for ours in ("embed", "final_norm", "lm_head"):
        if ours not in params:
            continue
        hf_name, transpose = inv_top[ours]
        arr = np.asarray(jax.device_get(params[ours]), dtype=np.float32)
        tensors[hf_name] = np.ascontiguousarray(arr.T) if transpose else arr
    n_exp = getattr(config, "num_experts", 0)
    router_name = hf_moe_names(config)[0]
    for ours, stacked in params["layers"].items():
        host = np.asarray(jax.device_get(stacked), dtype=np.float32)
        if n_exp and ours == "router":
            for l in range(host.shape[0]):
                tensors[f"model.layers.{l}.{router_name}"] = (
                    np.ascontiguousarray(host[l].T))
            continue
        if n_exp and ours in ("wg", "wu", "wd"):
            for l in range(host.shape[0]):
                for e in range(host.shape[1]):
                    tensors[hf_expert_name(l, e, ours, config)] = (
                        np.ascontiguousarray(host[l, e].T))
            continue
        hf_sub, transpose = {v[0]: (k, v[1]) for k, v in HF_LAYER_MAP.items()}[ours]
        for l in range(host.shape[0]):
            arr = host[l]
            tensors[f"model.layers.{l}.{hf_sub}"] = (
                np.ascontiguousarray(arr.T) if transpose else np.ascontiguousarray(arr))
    save_file(tensors, os.path.join(path, "model.safetensors"))
    hf_cfg = {
        "architectures": ["MixtralForCausalLM" if n_exp
                          else ("Qwen2ForCausalLM" if config.attention_bias
                                else "LlamaForCausalLM")],
        "attention_bias": config.attention_bias,
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "num_hidden_layers": config.num_layers,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "intermediate_size": config.intermediate_size,
        "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_eps,
        "tie_word_embeddings": config.tie_embeddings,
        "max_position_embeddings": config.max_position,
        "sliding_window": config.sliding_window,
        "head_dim": config.head_dim,
    }
    if n_exp:
        hf_cfg["num_local_experts"] = n_exp
        hf_cfg["num_experts_per_tok"] = config.num_experts_per_tok
    if getattr(config, "sparse", None) is not None:
        from symmetry_tpu.models.llama import hf_config_sparse

        hf_cfg = hf_config_sparse(config)
    with open(os.path.join(path, "config.json"), "w", encoding="utf-8") as fh:
        json.dump(hf_cfg, fh, indent=2)


# ---------------------------------------------------------------------------
# Warm restart cache (SURVEY §5.4: orbax-style cached sharded weights)
#
# Loading a big checkpoint costs safetensors streaming + HF-layout
# transposition + layer stacking + (for int8 serving) quantization of
# every matmul weight. All of it is deterministic in (checkpoint, dtype,
# quantize), so the first load persists the FINISHED param tree — stacked
# layers, our layout, already quantized — and every restart after that is
# a flat mmap read straight to device. No transposes, no quantize pass.

_WARM_DIR = ".symmetry_warm"
_WARM_VERSION = 1


def _warm_path(checkpoint_path: str, dtype, quantize: bool) -> str:
    tag = f"v{_WARM_VERSION}-{jnp.dtype(dtype).name}-{'int8' if quantize else 'dense'}"
    return os.path.join(checkpoint_path, _WARM_DIR, tag)


def _flatten_params(params: dict, prefix: str = "") -> Iterator[tuple[str, Any]]:
    from symmetry_tpu.ops.quant import (
        PackedQuantizedTensor, QuantizedTensor, unpack_quantized)

    for name, child in sorted(params.items()):
        path = f"{prefix}{name}"
        if isinstance(child, dict):
            yield from _flatten_params(child, path + "/")
        elif isinstance(child, PackedQuantizedTensor):
            # The cache stores the FLAT int8 layout: tile geometry is a
            # kernel tuning detail (tpu.fused_dequant re-packs at engine
            # construction), not checkpoint state — a cache written by a
            # fused build must stay readable by a non-fused one.
            flat = unpack_quantized(child)
            yield path + ":q", flat.q
            yield path + ":scale", flat.scale
        elif isinstance(child, QuantizedTensor):
            yield path + ":q", child.q
            yield path + ":scale", child.scale
        else:
            yield path, child


def _checkpoint_fingerprint(checkpoint_path: str) -> list[list]:
    """(name, mtime, size) of every source file the cache derives from —
    recorded at save, verified at load, so an overwritten checkpoint can
    never be silently served from a stale cache."""
    out = []
    for fname in sorted(os.listdir(checkpoint_path)):
        if fname.endswith(".safetensors") or fname in (
                "config.json", "model.safetensors.index.json"):
            st = os.stat(os.path.join(checkpoint_path, fname))
            out.append([fname, round(st.st_mtime, 3), st.st_size])
    return out


# Host-RAM guard for the cache WRITE: save_file needs the whole tree as
# host arrays at once. Int8-quantized 70B is ~35 GB — fine on TPU hosts —
# but an operator can cap or disable via this env var.
_WARM_MAX_BYTES = int(float(os.environ.get(
    "SYMMETRY_WARM_CACHE_MAX_GB", "64")) * 1e9)


def save_warm_cache(checkpoint_path: str, params: dict, config: ModelConfig,
                    *, dtype, quantize: bool) -> None:
    """Persist a finished param tree next to its checkpoint (best effort —
    failure to cache must never fail serving). bfloat16 leaves are stored
    as uint16 views with the dtype recorded, so the file has no
    non-numpy-native dtypes. The write is ATOMIC (temp dir + rename): a
    crash mid-save must leave no half-cache a later load could trip on."""
    import dataclasses
    import shutil
    import tempfile

    from safetensors.numpy import save_file

    total = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for _, leaf in _flatten_params(params))
    if total > _WARM_MAX_BYTES:
        raise RuntimeError(
            f"param tree is {total/1e9:.1f} GB > "
            f"SYMMETRY_WARM_CACHE_MAX_GB; not caching")

    out_dir = _warm_path(checkpoint_path, dtype, quantize)
    tensors: dict[str, np.ndarray] = {}
    dtypes: dict[str, str] = {}
    for name, leaf in _flatten_params(params):
        host = np.asarray(jax.device_get(leaf))
        dtypes[name] = str(leaf.dtype)
        if host.dtype.name not in ("float32", "float16", "int8", "int32",
                                   "uint16"):
            if host.dtype.itemsize != 2:
                # the uint16-view trick is only shape-preserving for
                # 2-byte dtypes; anything else must fail loudly here,
                # not corrupt shapes at load
                raise RuntimeError(
                    f"unsupported warm-cache dtype {host.dtype} for {name}")
            host = host.view(np.uint16)  # bfloat16 and friends
        tensors[name] = np.ascontiguousarray(host)
    os.makedirs(os.path.dirname(out_dir), exist_ok=True)
    tmp_dir = tempfile.mkdtemp(dir=os.path.dirname(out_dir))
    try:
        save_file(tensors, os.path.join(tmp_dir, "params.safetensors"))
        meta = {
            "version": _WARM_VERSION,
            "config_class": type(config).__name__,
            "config": dataclasses.asdict(config),
            "dtypes": dtypes,
            "fingerprint": _checkpoint_fingerprint(checkpoint_path),
        }
        with open(os.path.join(tmp_dir, "meta.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(meta, fh)
        if os.path.exists(out_dir):
            shutil.rmtree(out_dir)
        os.rename(tmp_dir, out_dir)
    except BaseException:
        shutil.rmtree(tmp_dir, ignore_errors=True)
        raise


def load_warm_cache(checkpoint_path: str, *, dtype, quantize: bool,
                    mesh=None, rules=None) -> tuple[dict, ModelConfig] | None:
    """Load a warm cache written by save_warm_cache; None when absent or
    unreadable (callers fall back to the full checkpoint load). Sharded
    meshes read per-device slices via make_array_from_callback, exactly
    like the cold path — each host only touches its own shards."""
    from symmetry_tpu.models.llama import ModelConfig as MC
    from symmetry_tpu.models.llama import MoEConfig
    from symmetry_tpu.ops.quant import QuantizedTensor

    out_dir = _warm_path(checkpoint_path, dtype, quantize)
    meta_path = os.path.join(out_dir, "meta.json")
    st_path = os.path.join(out_dir, "params.safetensors")
    if not (os.path.exists(meta_path) and os.path.exists(st_path)):
        return None
    try:
        with open(meta_path, "r", encoding="utf-8") as fh:
            meta = json.load(fh)
        if meta.get("version") != _WARM_VERSION:
            return None
        if meta.get("fingerprint") != _checkpoint_fingerprint(
                checkpoint_path):
            return None  # checkpoint changed since the cache was written
        cls = MoEConfig if meta["config_class"] == "MoEConfig" else MC
        config = cls(**meta["config"])
    except (ValueError, TypeError, KeyError, OSError):
        return None

    from safetensors import safe_open

    import ml_dtypes

    try:
        handle = safe_open(st_path, framework="np")
    except Exception:  # noqa: BLE001 — truncated/corrupt file → cold load
        return None
    dtypes = meta["dtypes"]

    if mesh is not None:
        from symmetry_tpu.models.llama import (
            param_logical_axes, quantized_logical_axes)

        axes = param_logical_axes(config)
        if quantize:
            axes = quantized_logical_axes(axes)
        shardings = shardings_for(axes, mesh, rules)
    else:
        dev = jax.devices()[0]
        shardings = None  # single device: whole-array reads

    def leaf_sharding(path_parts):
        node = shardings
        for part in path_parts:
            node = node[part] if isinstance(node, dict) else getattr(
                node, part)
        return node

    def read_leaf(name: str):
        want = np.dtype(ml_dtypes.bfloat16) if dtypes[name] == "bfloat16" \
            else np.dtype(dtypes[name])
        sl = handle.get_slice(name)

        def read(index):
            arr = sl[_norm_index(index, len(sl.get_shape()))]
            if arr.dtype == np.uint16 and want != np.uint16:
                arr = arr.view(want)
            return arr

        shape = tuple(sl.get_shape())
        if mesh is not None:
            parts = name.replace(":", "/").split("/")
            sharding = leaf_sharding(parts)
        else:
            sharding = jax.sharding.SingleDeviceSharding(dev)
        return jax.make_array_from_callback(shape, sharding, read)

    # rebuild the nested tree; ":q"/":scale" pairs fold into
    # QuantizedTensor leaves
    params: dict = {}
    pending_quant: dict[str, dict] = {}
    try:
        for name in handle.keys():
            arr = read_leaf(name)
            if ":" in name:
                base, _, part = name.partition(":")
                pending_quant.setdefault(base, {})[part] = arr
            else:
                _tree_set(params, name.split("/"), arr)
    finally:
        # every callback has run by now (make_array_from_callback is
        # synchronous) — release the fd/mmap of the multi-GB cache file
        # on EVERY path, including a failed read (the caller falls back
        # to the cold load and must not hold a stale mapping)
        if hasattr(handle, "__exit__"):
            handle.__exit__(None, None, None)
    for base, parts in pending_quant.items():
        _tree_set(params, base.split("/"),
                  QuantizedTensor(q=parts["q"], scale=parts["scale"]))
    return params, config


def _tree_set(tree: dict, parts: list[str], value) -> None:
    for p in parts[:-1]:
        tree = tree.setdefault(p, {})
    tree[parts[-1]] = value
