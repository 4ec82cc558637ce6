"""The chip benchmark of the TM train-and-serve path (see README.md)."""
