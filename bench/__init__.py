"""The chip benchmark of the content-addressed store (see run.py)."""
