"""The chip benchmark's library: traffic, weights, the system under test,
the client, trace reduction, counts, peaks and the correctness check.
``benchmarks/chip/run.py`` is its command line."""
