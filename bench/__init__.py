"""The chip benchmark: harness, traffic, reference, trace reduction."""
