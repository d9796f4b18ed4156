"""Chip benchmark of the PAM serving path (see BENCHMARK.json)."""
