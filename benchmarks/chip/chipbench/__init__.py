"""The chip benchmark's library: generator, SLO arithmetic, FLOP counts,
trace reduction, weights, the loop and the comparison for ``correct``."""
