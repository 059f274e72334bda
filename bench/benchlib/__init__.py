"""The chip benchmark's library: files by name, traffic, FLOP and byte
counts, peaks, the trace reduction, the comparison with the reference,
and the run of one cell."""
