"""The chip benchmark of the BlinkQL serving path (see run.py)."""
