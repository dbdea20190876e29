"""On-chip benchmark of the fault-tolerant QR engine (see run.py)."""
