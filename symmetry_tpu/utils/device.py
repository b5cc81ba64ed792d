"""Which device an engine got, and the rule that it must be a chip.

JAX does not fail when it cannot get the TPU (none attached, or another
process holds it): it warns and hands back the CPU. An engine built there
would initialise a 7B model on the host and report ready. So every engine
build passes through `require_chip`, and every engine process reports what
it got (`device_report`) in its READY frame, log line and stats — nothing
downstream has to guess, and nothing touches JAX to find out.
"""

from __future__ import annotations

from typing import Any

import jax


class NoChipError(RuntimeError):
    """The engine's devices are not TPUs and the CPU was not asked for."""


def require_chip() -> None:
    """Refuse any platform but `tpu` — unless JAX was pinned to the CPU
    BY NAME (`jax_platforms == "cpu"`, which JAX fills from
    `JAX_PLATFORMS`). That is what tests/conftest.py and the tools/
    smokes do (and a CPU rehearsal of chip_smoke.py); a CPU that JAX fell
    back to on its own never serves. Where JAX is pinned to the TPU and
    cannot get it, backend initialisation itself fails; that is the same
    refusal."""
    try:
        device = jax.local_devices()[0]
    except RuntimeError as exc:
        raise NoChipError(f"JAX could not initialise its backend: {exc}"
                          ) from exc
    if device.platform == "tpu" or jax.config.jax_platforms == "cpu":
        return
    raise NoChipError(
        f"engine got platform {device.platform} ({device.device_kind}), "
        f"not tpu: no chip is attached, or another process holds it — "
        f"each engine process needs a chip of its own. (To run on the CPU "
        f"on purpose, pin it by name: JAX_PLATFORMS=cpu.)")


def device_report() -> dict[str, Any]:
    """What this process runs on, as JAX reports it: platform, kind,
    count, and per-device HBM in use / limit (`memory_stats()`; the CPU
    backend reports none, so `hbm` is empty there)."""
    devices = jax.local_devices()
    hbm = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "bytes_in_use" in stats:
            hbm.append({"bytes_in_use": int(stats["bytes_in_use"]),
                        "bytes_limit": int(stats.get("bytes_limit", 0))})
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "hbm": hbm}


def memory_report() -> list[dict[str, int]] | None:
    """Each local device's `memory_stats()` as the runtime gives them
    (`bytes_in_use`, `peak_bytes_in_use`, `largest_free_block_bytes`,
    `num_allocs`, ...); None where it gives none (the CPU)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not any(stats):
        return None
    return [{k: int(v) for k, v in (s or {}).items()} for s in stats]
