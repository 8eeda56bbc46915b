"""Device time per call of the decode-step program (layer: model step)."""
from bench.metrics._device import decode_step_ms as read  # noqa: F401
