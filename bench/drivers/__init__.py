"""One module per driver kind, found by the traffic file's ``driver``."""
