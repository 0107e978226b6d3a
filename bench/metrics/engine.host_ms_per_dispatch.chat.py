"""engine.host_ms_per_dispatch.chat: see benchlib.readers.engine_host_ms_per_dispatch."""
from benchlib.readers import engine_host_ms_per_dispatch as read  # noqa: F401
