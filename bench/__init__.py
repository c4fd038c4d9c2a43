"""The benchmark: harness, cells and yardstick (see bench/README.md)."""
