"""decode.device_ms_per_step.chat: see benchlib.attribution.decode_device_ms_per_step."""
from benchlib.attribution import decode_device_ms_per_step as read  # noqa: F401
