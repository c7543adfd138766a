"""FL-APU benchmark harness (see bench/README.md)."""
