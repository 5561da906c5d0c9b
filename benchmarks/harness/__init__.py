"""Harness of the benchmark: everything general; nothing here names a cell."""
