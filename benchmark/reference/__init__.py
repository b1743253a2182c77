"""Plain references of the benchmark's configurations (see ``common.py``)."""
