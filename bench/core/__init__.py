"""The benchmark's yardstick: graph generation, the FLOP count, the trace
reduction, the plain float32 reference and the comparison that decides
``correct``.  Nothing here imports the program under test except
``session.py``, which drives it."""
