"""Tests of the benchmark's harness, run on the CPU at small sizes."""
