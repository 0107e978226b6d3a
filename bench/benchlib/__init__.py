"""Shared, generic parts of the benchmark (see ``bench/run.py``)."""
