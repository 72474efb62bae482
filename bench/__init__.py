"""The on-chip benchmark: harness, traffic generator, reference and
per-layer metric readers (see bench/README.md)."""
