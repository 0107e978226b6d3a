"""prefill.ms_per_ktok.chat: see benchlib.readers.prefill_ms_per_ktok."""
from benchlib.readers import prefill_ms_per_ktok as read  # noqa: F401
