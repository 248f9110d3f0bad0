"""The Gear kernel's share of the chip's HBM bound: the window's user
bytes read once at the published HBM bandwidth (``bench/work.py``) over
the Gear kernel's device time in the trace, in percent."""
from bench import work


def read(ctx):
    tr, ing = ctx.get("trace"), ctx.get("ingest")
    if not tr or not ing or not ing["user_bytes"]:
        return None
    kernel_s = tr["kernel_s"].get("gear", 0.0)
    if kernel_s <= 0:
        return None
    least_s = work.gear_hbm_bytes(ing["user_bytes"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
