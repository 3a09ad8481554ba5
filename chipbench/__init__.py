"""On-chip benchmark of the HNTL vector store (see ``run.py``)."""
