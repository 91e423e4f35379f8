"""On-chip benchmark of LocalAdaSEG's Parameter-Server training: one cell
per (configuration, traffic mix), run by ``perfbench/run.py``."""
