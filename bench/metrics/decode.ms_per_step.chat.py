"""decode.ms_per_step.chat: see benchlib.readers.decode_ms_per_step."""
from benchlib.readers import decode_ms_per_step as read  # noqa: F401
