"""The MD5 kernel's share of the chip's HBM bound: the least time the
window's block digests could take at the published HBM bandwidth
(user bytes read plus 16 bytes written per block, ``bench/work.py``)
over the MD5 kernel's device time in the trace, in percent.  Only the
HBM bound: no VPU integer peak is published for the chip."""
from bench import work


def read(ctx):
    tr, ing = ctx.get("trace"), ctx.get("ingest")
    if not tr or not ing or not ing["user_bytes"]:
        return None
    kernel_s = tr["kernel_s"].get("md5", 0.0)
    if kernel_s <= 0:
        return None
    least_s = work.md5_hbm_bytes(ing["user_bytes"], ing["blocks"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
