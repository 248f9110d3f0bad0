"""The work each kernel has to do, counted from user bytes.

Counted from what the user wrote, never from padded launch shapes or
the program's counters, so the count stays the same whatever
implements the kernel: a change that removes padding, trailers or
fingerprint output lowers the kernel's time and raises its share, and
cannot push a share past 100% by shrinking the count.

* MD5 over a block reads the block once and writes a 16-byte digest.
* Gear reads each byte once; what it must hand on is at most a few
  boundaries per MiB, so its output is not counted.
"""
from __future__ import annotations

DIGEST_BYTES = 16


def md5_hbm_bytes(user_bytes: int, blocks: int) -> int:
    return int(user_bytes) + DIGEST_BYTES * int(blocks)


def gear_hbm_bytes(user_bytes: int) -> int:
    return int(user_bytes)
