"""Chip benchmark of DP training: harness, yardstick and plain references."""
