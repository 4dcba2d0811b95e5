"""Harness: cells, generator, capture, trace reduction, comparison."""
