"""Shared pieces of the benchmark: discovery, statistics, traffic, trace
reduction and the chip checks."""
