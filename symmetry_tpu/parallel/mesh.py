"""Device mesh construction.

Axis convention (ordered outer→inner so the innermost axis maps to the
fastest interconnect — `model` collectives ride ICI, `data` may span DCN,
per the two-tier design in SURVEY §5.8):

    stage   — no schedule uses it: the engine refuses a mesh with
              stage > 1 (engine/engine.py). It stays in AXIS_ORDER because
              the axis names are part of every lowered program under
              Shardy (`sdy.mesh`); it leaves in the one mesh change that
              decides `context` too (ROADMAP Design 6)
    data    — batch replication/sharding; DCN-safe (no per-layer collectives)
    context — sequence/ring-attention axis (long context, SURVEY §5.7)
    expert  — MoE expert parallelism (models/moe.py); ICI collectives
    model   — tensor parallelism; all-reduce per layer, must stay on ICI

A provider.yaml `tpu.mesh` mapping like {"data": 2, "model": 4} becomes a
MeshSpec; axes of size 1 are still materialized so PartitionSpecs can always
name them (XLA treats size-1 axes as free).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("stage", "data", "context", "expert", "model")


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape, e.g. MeshSpec(data=1, model=8)."""

    stage: int = 1
    data: int = 1
    context: int = 1
    expert: int = 1
    model: int = 1

    @classmethod
    def from_dict(cls, raw: dict[str, int]) -> "MeshSpec":
        unknown = set(raw) - set(AXIS_ORDER)
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}; valid: {AXIS_ORDER}")
        return cls(**{k: int(v) for k, v in raw.items()})

    @property
    def size(self) -> int:
        size = 1
        for axis in AXIS_ORDER:
            size *= getattr(self, axis)
        return size

    def shape(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}


def build_mesh(spec: MeshSpec | dict[str, int], devices=None) -> Mesh:
    """Build a Mesh from the spec over `devices` (default: all available).

    Device order follows jax.devices(), which on TPU enumerates in
    ICI-topology order — consecutive devices are ICI neighbours, so putting
    `model` innermost keeps its all-reduces on ICI.
    """
    if isinstance(spec, dict):
        spec = MeshSpec.from_dict(spec)
    if devices is None:
        devices = jax.devices()
    if spec.size > len(devices):
        raise ValueError(f"mesh needs {spec.size} devices, have {len(devices)}")
    grid = np.asarray(devices[: spec.size]).reshape(
        tuple(getattr(spec, a) for a in AXIS_ORDER)
    )
    return Mesh(grid, AXIS_ORDER)
