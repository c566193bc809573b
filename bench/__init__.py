"""The on-chip benchmark of FREYJA join discovery (see run.py)."""
