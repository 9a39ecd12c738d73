"""The plain reference: every interaction template in numpy, independent of
the program (see ``frames.py``)."""
from .frames import Reference, Result

__all__ = ["Reference", "Result"]
