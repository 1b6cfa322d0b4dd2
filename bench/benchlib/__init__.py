"""The benchmark's own library: cell specs, weights and traffic from the
seed, the plain reference, the trace reduction, FLOP and byte counts and
the table of peaks. Nothing here is imported by the program under test,
and nothing here changes when the program does."""
