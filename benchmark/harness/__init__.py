"""Shared machinery of the benchmark: manifest lookup, device checks,
metric arithmetic, peaks, FLOPs from shapes, the trace reduction."""
