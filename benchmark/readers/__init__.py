"""Readers of per-layer metrics, one module each, found by name.

``read(ctx, params)`` returns the metric's value, or None where the run
holds nothing to read it from (the harness then leaves the metric out).
"""
