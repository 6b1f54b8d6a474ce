"""The chip benchmark: see bench/run.py and PERF.md."""
