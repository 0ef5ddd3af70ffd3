"""The benchmark: one command runs one cell once (see ``bench/run.py``)."""
