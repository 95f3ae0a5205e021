"""The benchmark's harness: cells, traffic, yardsticks, trace reduction
and the correctness check.  Nothing here is imported by the program."""
