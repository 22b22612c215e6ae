"""The on-chip benchmark of the AMPLE GNN serving stack (see ``run.py``)."""
