"""The chips a run is given, and their published peaks."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: no result."""


def peaks_for(device_kind: str, path: Path = PEAKS) -> Dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}: add them with their source")
    return table[device_kind]


def require_chips(chips: int) -> List:
    """The first ``chips`` TPU devices; raises NoChip otherwise.  JAX may
    fall back to the CPU when it finds no TPU, so the platform is checked,
    not assumed."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"need a TPU, JAX found {devices[0].platform} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> Dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes_in_use(devices) -> int:
    """The runtime's ``peak_bytes_in_use`` of the fullest chip, 0 where
    not reported.  On a TPU it counts the buffers the program holds
    (weights, rows, outputs) but not a compiled program's scratch, so it
    stays near the weights' size (PERF.md, section 2)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def plan_bytes(fn, args) -> Optional[int]:
    """The compiler's plan for one call of the jitted ``fn`` on ``args``
    (shapes and placements), per chip: arguments + outputs - aliased +
    scratch, from ``memory_analysis()`` of the compiled program."""
    m = fn.lower(*args).compile().memory_analysis()
    if m is None:
        return None
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)
