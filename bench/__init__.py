"""The on-chip benchmark: one run of one cell per process (`run_cell.py`)."""
