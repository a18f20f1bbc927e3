"""Adaptors from a cell to the program's served entry points."""
