"""decode.live_row_share.chat: see benchlib.attribution.decode_live_row_share."""
from benchlib.attribution import decode_live_row_share as read  # noqa: F401
