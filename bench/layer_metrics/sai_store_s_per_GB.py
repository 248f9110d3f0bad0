"""Seconds the SAI's store stage took per GB (10**9 bytes) of user data,
summed over the writes of the window (``WriteStats.stage_s["store"]``)."""


def read(ctx):
    ing = ctx.get("ingest")
    if not ing or not ing["user_bytes"]:
        return None
    total = sum(st.stage_s.get("store", 0.0) for st in ing["write_stats"])
    return total / (ing["user_bytes"] / 1e9)
