"""Shared code of the chip benchmark: cell files, device, traffic, traces.

Everything that belongs to one configuration, traffic mix or per-layer metric
lives in a file of its own under ``benchmarks/chip`` and is found by the name
``BENCHMARK.json`` gives it; this package holds only what all cells share.
"""
