"""The on-chip benchmark: harness, data generator, references, trace
reduction and per-metric readers. Entry point: ``bench/run.py``."""
