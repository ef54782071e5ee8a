"""On-chip benchmark of the spatial search server (see bench/README.md)."""
