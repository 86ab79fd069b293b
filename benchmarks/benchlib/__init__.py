"""Shared parts of the benchmark harness (no model, cell or metric named)."""
