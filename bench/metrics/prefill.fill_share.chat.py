"""prefill.fill_share.chat: see benchlib.attribution.prefill_fill_share."""
from benchlib.attribution import prefill_fill_share as read  # noqa: F401
