"""Multi-host serving: one logical provider backed by N JAX processes.

The reference's only multi-node story was many independent single-node
providers behind server routing (SURVEY §2.3). A multi-host TPU pod is
different: N host processes each own a slice of the devices, every jitted
computation must be entered by ALL processes in the same order, and only
rank 0 fronts the P2P network. Three pieces (SURVEY §7 stage 6 +
hard-part 2):

  1. `init_distributed` — jax.distributed bring-up (coordinator address,
     process count, rank), after which jax.devices() is the GLOBAL device
     set and arrays can span hosts.
  2. `build_multihost_mesh` — a hybrid mesh whose `data` axis spans hosts
     over DCN (no per-layer collectives cross hosts) while `context`/
     `model` stay inside each host's ICI domain (mesh_utils topology-aware
     ordering).
  3. `CommandLoop` — the rank-0 control plane: rank 0 decides engine calls
     (prefill/insert/decode/stop) from its scheduler; every process —
     including rank 0 — receives each command via a device-fabric broadcast
     and enters the identical jitted call. Workers never see the network.

Commands ride `multihost_utils.broadcast_one_to_all` as one fixed-shape
int32 vector (jit-friendly: same shape every step, no pickled metadata on
the hot path).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from symmetry_tpu.parallel.mesh import AXIS_ORDER, MeshSpec
from symmetry_tpu.utils.logging import logger as log

# Command kinds (slot 0 of the broadcast vector).
CMD_IDLE = 0      # no-op heartbeat (keeps workers in lockstep while empty)
CMD_PREFILL = 1   # prefill + insert one request
CMD_DECODE = 2    # advance all slots one decode block
CMD_STOP = 3      # shut down the loop
CMD_WARMUP = 4    # precompile the decode program (pre-traffic)
CMD_RELEASE = 5   # a finished slot: the next decode program parks its lane

# Vector layout: [kind, slot, true_len, bucket, temp_milli, top_p_milli,
#                 top_k, seed_or_-1, tokens...(max_bucket)]
_HEADER = 8


_distributed_up = False


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int, **kwargs: Any) -> None:
    """Bring up jax.distributed (idempotent per process — a provider
    restart re-enters this; jax raises on a second initialize)."""
    global _distributed_up
    if _distributed_up:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
    _distributed_up = True
    log.info(
        f"jax.distributed up: rank {process_id}/{num_processes}, "
        f"{jax.local_device_count()} local / {jax.device_count()} global devices")


def build_multihost_mesh(ici: MeshSpec | dict, dcn_data: int = 1):
    """Mesh whose `data` axis spans hosts (DCN) and the rest ICI.

    In a multi-process job the mesh MUST cover every global device — a mesh
    that misses a process leaves that rank with no addressable shard of any
    engine array, which fails at the first host read. `ici` describes ONE
    host's slice; dcn_data is the number of hosts on the data axis.
    """
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if isinstance(ici, dict):
        ici = MeshSpec.from_dict(ici)
    total = dcn_data * ici.size
    if jax.process_count() > 1 and total != jax.device_count():
        raise ValueError(
            f"multihost mesh ({dcn_data} hosts × ici {ici.shape()}) covers "
            f"{total} devices but the job has {jax.device_count()} — every "
            f"global device must be in the mesh")
    ici_shape = tuple(getattr(ici, a) for a in AXIS_ORDER)
    # data is the DCN-crossing axis (stage PP over DCN would be the other
    # legal choice; this helper builds data-over-DCN meshes)
    dcn_shape = tuple(dcn_data if a == "data" else 1 for a in AXIS_ORDER)
    if dcn_data > 1:
        try:
            # TPU pods: DCN granule = slice (device.slice_index).
            devices = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=jax.devices())
        except ValueError:
            # Backends without slice indices (CPU tests, single-slice jobs
            # spanning hosts): granule = process.
            devices = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=jax.devices(),
                process_is_granule=True)
    else:
        devices = mesh_utils.create_device_mesh(ici_shape,
                                                devices=jax.devices()[:ici.size])
    return Mesh(devices, AXIS_ORDER)


@dataclass
class Command:
    kind: int
    slot: int = 0
    true_len: int = 0
    bucket: int = 0
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    seed: int | None = None
    tokens: np.ndarray | None = None  # [true_len] int32

    def encode(self, max_bucket: int) -> np.ndarray:
        vec = np.zeros((_HEADER + max_bucket,), np.int32)
        vec[0] = self.kind
        vec[1] = self.slot
        vec[2] = self.true_len
        vec[3] = self.bucket
        vec[4] = int(self.temperature * 1000)
        vec[5] = int(self.top_p * 1000)
        vec[6] = self.top_k
        vec[7] = -1 if self.seed is None else self.seed
        if self.tokens is not None:
            vec[_HEADER:_HEADER + len(self.tokens)] = self.tokens
        return vec

    @classmethod
    def decode(cls, vec: np.ndarray) -> "Command":
        kind, slot, true_len, bucket = (int(vec[0]), int(vec[1]),
                                        int(vec[2]), int(vec[3]))
        seed = int(vec[7])
        return cls(
            kind=kind, slot=slot, true_len=true_len, bucket=bucket,
            temperature=vec[4] / 1000.0, top_p=vec[5] / 1000.0,
            top_k=int(vec[6]), seed=None if seed < 0 else seed,
            tokens=np.asarray(vec[_HEADER:_HEADER + true_len], np.int32),
        )


class CommandLoop:
    """Lockstep engine driver: rank 0 leads, all ranks follow.

    Rank 0 calls `lead(cmd)`; workers run `follow_forever()`. Both paths
    end in identical `InferenceEngine` method calls, which is what keeps
    every process entering the same jitted computations in the same order
    (the SPMD contract of multi-host JAX).
    """

    def __init__(self, engine, *, is_coordinator: bool) -> None:
        self.engine = engine
        self.is_coordinator = is_coordinator
        self.max_bucket = max(engine.prefill_buckets)

    # -------------------------------------------------------------- shared

    def _execute(self, cmd: Command):
        from symmetry_tpu.engine.engine import SamplingParams

        if cmd.kind == CMD_PREFILL:
            sampling = SamplingParams(
                temperature=cmd.temperature, top_p=cmd.top_p,
                top_k=cmd.top_k, seed=cmd.seed)
            return self.engine.prefill_and_insert(
                cmd.slot, list(map(int, cmd.tokens)), sampling)
        if cmd.kind == CMD_DECODE:
            return self.engine.decode_steps()
        if cmd.kind == CMD_WARMUP:
            return self.engine.warmup()
        if cmd.kind == CMD_RELEASE:
            return self.engine.release_slot(cmd.slot)
        return None

    def _broadcast(self, vec: np.ndarray) -> np.ndarray:
        from jax.experimental import multihost_utils

        return np.asarray(
            multihost_utils.broadcast_one_to_all(vec,
                                                 is_source=self.is_coordinator))

    # -------------------------------------------------------------- rank 0

    def lead(self, cmd: Command):
        """Broadcast a command and execute it locally (rank 0 only).

        Executes the DECODED round-trip of the wire bytes, not the original
        command — the milli-unit quantization of temperature/top_p must be
        identical on every rank or the replicated state diverges.
        """
        assert self.is_coordinator
        vec = cmd.encode(self.max_bucket)
        self._broadcast(vec)
        return self._execute(Command.decode(vec))

    def idle_tick(self) -> None:
        """Heartbeat while no requests are active: workers sit inside the
        broadcast collective, and distributed runtimes time out a collective
        that rank 0 never enters — tick it periodically."""
        assert self.is_coordinator
        self._broadcast(Command(kind=CMD_IDLE).encode(self.max_bucket))

    def stop(self) -> None:
        if self.is_coordinator:
            self._broadcast(Command(kind=CMD_STOP).encode(self.max_bucket))

    # -------------------------------------------------------------- workers

    def follow_forever(self) -> None:
        """Worker loop: receive and mirror rank 0's engine calls."""
        assert not self.is_coordinator
        zero = np.zeros((_HEADER + self.max_bucket,), np.int32)
        while True:
            cmd = Command.decode(self._broadcast(zero))
            if cmd.kind == CMD_STOP:
                return
            self._execute(cmd)


class MultihostEngine:
    """Engine facade for the scheduler on rank 0: every call is led through
    the CommandLoop so worker processes stay in lockstep. Exposes the same
    surface Scheduler uses (prefill_and_insert / decode_steps / metadata),
    in its synchronous form only: a command round completes before it
    returns, so an admission's first token is a host value when the
    scheduler queues it and the later read is a no-op (as for
    decode_steps_dispatch below).
    """

    def __init__(self, loop: CommandLoop) -> None:
        self._loop = loop
        eng = loop.engine
        self.tokenizer = eng.tokenizer
        self.max_slots = eng.max_slots
        self.max_seq_len = eng.max_seq_len
        self.decode_block = eng.decode_block
        self.slot_capacity = eng.slot_capacity
        self.prefill_buckets = eng.prefill_buckets

    def prefill_and_insert(self, slot: int, prompt_ids, sampling) -> int:
        n = len(prompt_ids)
        bucket = self._loop.engine.bucket_for(n)
        seed = sampling.seed
        if seed is None:
            # Pin per-request entropy HERE: each process has different local
            # entropy, and an unseeded prefill executed per-process would
            # diverge the replicated state. Rank 0 chooses, all follow.
            seed = int.from_bytes(os.urandom(3), "little")
        # Client-controlled: fold into the non-negative int32 range the wire
        # slot carries (negative would decode as None → per-rank entropy;
        # >= 2^31 would overflow before the broadcast).
        seed = seed % (2**31)
        cmd = Command(
            kind=CMD_PREFILL, slot=slot, true_len=n, bucket=bucket,
            temperature=sampling.temperature, top_p=sampling.top_p,
            top_k=sampling.top_k, seed=seed,
            tokens=np.asarray(prompt_ids, np.int32))
        return self._loop.lead(cmd)

    def decode_steps(self) -> np.ndarray:
        return self._loop.lead(Command(kind=CMD_DECODE))

    def decode_steps_dispatch(self) -> np.ndarray:
        """Scheduler's double-buffer hook. Multihost decode must complete
        the cross-process command round before returning, so there is no
        async lookahead here — the already-materialized token block is
        returned and the scheduler's np.asarray on it is a no-op."""
        return self.decode_steps()

    def release_slot(self, slot: int) -> None:
        """Every process notes the lane: the lanes to park are an input
        of the next decode program and must be the same everywhere."""
        self._loop.lead(Command(kind=CMD_RELEASE, slot=slot))

    def warmup(self) -> None:
        self._loop.lead(Command(kind=CMD_WARMUP))

    def idle_tick(self) -> None:
        self._loop.idle_tick()

    def slot_length(self, slot: int) -> int:
        return self._loop.engine.slot_length(slot)

    def bucket_for(self, prompt_len: int) -> int:
        """Host-side validation only — no broadcast needed."""
        return self._loop.engine.bucket_for(prompt_len)
