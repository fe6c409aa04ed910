"""On-chip benchmark: harness, traffic, reference and metric readers."""
