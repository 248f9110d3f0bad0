"""Plain references the benchmark holds the store to.

Written from the store's stated semantics, not from its code: nothing
here imports ``repro`` or JAX, and nothing takes a table, digest or
boundary that the program computed.

A configuration names its reference module (``"reference": "<r>"`` ->
``bench/reference/<r>.py``), which defines ``chunk_ends(data, sai)``,
``digests(data, ends)`` and ``block_bytes(sai, object_bytes)``, the
range of the longest block of one write, from which the launch shapes
to warm up follow.
"""
