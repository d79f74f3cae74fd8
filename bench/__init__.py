"""On-chip benchmark of the planner-placed model path (see PERF.md)."""
