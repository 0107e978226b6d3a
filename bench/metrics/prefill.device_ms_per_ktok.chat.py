"""prefill.device_ms_per_ktok.chat: see benchlib.attribution.prefill_device_ms_per_ktok."""
from benchlib.attribution import prefill_device_ms_per_ktok as read  # noqa: F401
