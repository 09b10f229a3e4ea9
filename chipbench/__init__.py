"""The on-chip benchmark of the risk.v1 serving path (see PERF.md)."""
