"""The chip benchmark: one harness (``bench/run.py``) driven by data files
found by name (``bench/harness.py``)."""
