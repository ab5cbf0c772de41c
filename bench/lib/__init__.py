"""The yardstick the benchmark keeps for itself: traffic, references,
counts of work, peaks and the trace reduction."""
