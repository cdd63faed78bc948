"""The on-chip benchmark of the CHL system: harness, traffic drivers,
per-layer metric readers, and the yardstick's own data and reference.
``python bench/run.py --help`` runs one cell."""
