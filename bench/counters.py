"""What the per-layer metrics read from the program's own counters: the
change over the window of the engine's per-device counters
(``snapshot_stats()["per_device"]``, taken before and after the window)
and sums of the SAI's per-write stage seconds (``WriteStats.stage_s``).
Each returns None where the program keeps no such counter."""
from __future__ import annotations

from typing import Callable, Dict, Optional


def engine_delta(ctx: Dict, read: Callable[[Dict], float]) -> Optional[float]:
    """Change over the window of ``read(row)`` summed over devices."""
    try:
        before, after = (sum(read(row) for row in ctx[key]["per_device"]
                             .values())
                         for key in ("engine_before", "engine_after"))
    except KeyError:
        return None
    return after - before


def phase_s(*phases: str) -> Callable[[Dict], float]:
    """A stats-row reader: the summed seconds of ``phases`` over every
    kind of launch."""
    return lambda row: sum(by_phase.get(p, 0.0)
                           for by_phase in row["phase_s"].values()
                           for p in phases)


def user_bytes(ctx: Dict) -> int:
    ing = ctx.get("ingest")
    return ing["user_bytes"] if ing else 0


def per_user_gb(ctx: Dict, value: Optional[float]) -> Optional[float]:
    """``value`` per GB (10**9 bytes) of the window's user data."""
    if value is None or not user_bytes(ctx):
        return None
    return value / (user_bytes(ctx) / 1e9)


def stage_s(ctx: Dict, key: str) -> Optional[float]:
    """``stage_s[key]`` summed over the window's writes; None where no
    write has the key."""
    ing = ctx.get("ingest")
    if not ing or not any(key in st.stage_s for st in ing["write_stats"]):
        return None
    return sum(st.stage_s.get(key, 0.0) for st in ing["write_stats"])
