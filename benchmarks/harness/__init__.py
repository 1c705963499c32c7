"""The benchmark's own code, as far as it is the same for every architecture:
traffic, drivers, trace reduction, peaks, seeds and draws, the reference's
optimizer and gap arithmetic, and the last line. What is one architecture's
own (leaves, forward, counts, the model the program builds) is its family's,
`benchmarks/families/<model_type>/`. From the program the benchmark takes the
system under test and its counters, nothing else."""
