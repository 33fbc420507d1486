"""On-chip serving benchmark: one harness driven by the data files in this
directory (see ``run.py``)."""
