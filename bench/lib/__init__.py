"""Shared yardstick code: peaks, costs, trace reduction, spans, harness."""
