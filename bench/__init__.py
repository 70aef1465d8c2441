"""Benchmark of the simulator on the chip: see run.py."""
