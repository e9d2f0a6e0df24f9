"""The chip benchmark of the MWIS reducer: harness, reference and readers."""
