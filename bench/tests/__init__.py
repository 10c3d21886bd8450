"""Tests of the benchmark itself (CPU)."""
