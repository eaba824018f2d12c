"""On-chip benchmark of the NumS block runtime: ``python bench/run.py --help``."""
