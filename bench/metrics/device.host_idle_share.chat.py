"""device.host_idle_share.chat: see benchlib.attribution.host_idle_share."""
from benchlib.attribution import host_idle_share as read  # noqa: F401
