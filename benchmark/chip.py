"""What every runner kind asks of the machine: the cell's chips or a
refusal, the chip's published peaks or a refusal, the peak memory held.
There is no CPU fallback: only a rehearsal, which can never print
`correct: true`, runs off the TPU."""

from __future__ import annotations

import json
import os


def devices_for(chips: int, rehearse: bool) -> list:
    """The first `chips` devices, or a reason to stop."""
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def peak_for(kind: str, root: str) -> dict:
    """The `device_kind`'s entry of `peaks.json`; an unlisted kind is an
    error, not a default."""
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise SystemExit(f"device_kind {kind!r} is not in "
                         "benchmark/peaks.json: add it with its source")
    return peaks[kind]


def device_line(devs: list) -> dict:
    """The result line's `device`, as JAX reports it. `memory_peak_bytes`
    is what the fullest chip can give to nothing else at the peak: the
    allocator's peak plus what loaded programs hold for their
    temporaries, which the allocator's own peak leaves out (on a TPU v5
    lite, `peak_bytes_reserved`: 0 before the first program is loaded,
    the compiled step's `temp_size_in_bytes` after)."""
    stats = [d.memory_stats() or {} for d in devs]
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(
            m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
            for m in stats),
    }
