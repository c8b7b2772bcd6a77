"""The benchmark's harness: cells found by name, traffic, weights, the
serving and training loops, and the reduction from traces and counters
to metrics. It imports the program under test only to drive it."""
