"""The benchmark's own code: traffic, drivers, trace reduction, peaks, the
operation and byte counts, the plain reference and the last line. From the
program it takes the system under test and its counters, nothing else."""
