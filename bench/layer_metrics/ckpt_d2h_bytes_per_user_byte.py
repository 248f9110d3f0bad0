"""Bytes the window's saves pulled from the devices per byte of state
saved: ``WriteStats.d2h_bytes`` (the engine's digests, 16 per block, and
the blocks the store claimed as new) summed over the window's writes,
over the state bytes of its acknowledged saves."""
from bench import counters


def read(ctx):
    ing = ctx.get("ingest")
    if not ing or not counters.user_bytes(ctx) or not any(
            hasattr(st, "d2h_bytes") for st in ing["write_stats"]):
        return None
    return sum(getattr(st, "d2h_bytes", 0) for st in ing["write_stats"]) \
        / counters.user_bytes(ctx)
