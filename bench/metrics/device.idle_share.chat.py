"""device.idle_share.chat: see benchlib.readers.device_idle_share."""
from benchlib.readers import device_idle_share as read  # noqa: F401
