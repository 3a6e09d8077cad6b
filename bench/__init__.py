"""Chip benchmark of the served bytes-to-verdict path (see PERF.md)."""
