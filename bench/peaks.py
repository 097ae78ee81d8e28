"""Peak compute and memory bandwidth per chip, keyed by JAX's
``device_kind``, and the roofline time of a piece of work."""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple

TABLE = Path(__file__).resolve().parent / "peaks.json"


class Peak(NamedTuple):
    flops_per_s: float
    hbm_bytes_per_s: float


def peak(device_kind: str, table: Path = TABLE) -> Peak:
    """The chip's peaks; a kind missing from the table is an error."""
    with open(table) as f:
        rows = json.load(f)
    if device_kind not in rows:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table.name}; it has {sorted(rows)}")
    row = rows[device_kind]
    return Peak(float(row["flops_per_s"]), float(row["hbm_bytes_per_s"]))


def roofline_s(flops: float, nbytes: float, pk: Peak) -> tuple[float, str]:
    """The least time the chip could take for the work, and which bound
    sets it: ``"compute"`` or ``"memory"``."""
    t_compute = flops / pk.flops_per_s
    t_memory = nbytes / pk.hbm_bytes_per_s
    return (t_compute, "compute") if t_compute >= t_memory \
        else (t_memory, "memory")
