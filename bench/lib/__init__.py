"""Shared code of the benchmark: spec loading, traffic, weights, the plain
reference, trace reduction, peaks and operation counts. Nothing here is
specific to one cell, configuration, traffic mix or metric."""
