"""Chip benchmark of the DLaaS platform: one command, driven by the
entries of ``BENCHMARK.json`` and the data files beside this package."""
