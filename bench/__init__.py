"""Chip benchmark of the stage-graph stream path (see BENCHMARK.json)."""
