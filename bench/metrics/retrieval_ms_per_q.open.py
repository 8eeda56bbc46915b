"""Retrieval device time per query (layer: retrieval): device time of the
retrieval programs in the traced slice over the queries retrieved there."""
from bench.metrics._device import retrieval_ms_per_q as read  # noqa: F401
