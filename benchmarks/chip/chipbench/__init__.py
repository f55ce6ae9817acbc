"""The chip benchmark's library: the yardstick that later changes to the
program are measured with (inputs, weights, references' comparison, peaks,
operation counts and the trace reduction)."""
