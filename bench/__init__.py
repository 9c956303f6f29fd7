"""Serving benchmark of this repository: harness, traffic, reference."""
