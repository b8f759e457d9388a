"""Per-cell drivers, loaded by file name from a workload's ``driver``."""
