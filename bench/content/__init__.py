"""What writer streams send: a mix's ``content.kind`` names a module
here, whose ``Source(traffic, seed)`` makes the objects (see
``bench/generator.py``)."""
