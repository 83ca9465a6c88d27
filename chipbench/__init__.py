"""On-chip benchmark of the MIDX training and serving paths (`run.py`)."""
