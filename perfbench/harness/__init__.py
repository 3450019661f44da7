"""Harness of the chip benchmark: spec lookup, load generation, the run
loop, trace reduction, the plain reference and the output check."""
