"""On-chip benchmark of the orchestrator: see ``run_cell.py``."""
