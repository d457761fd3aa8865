"""The chip benchmark: harness, yardstick and reference (see ../run.py)."""
