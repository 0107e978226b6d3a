"""step_mfu.chat: see benchlib.readers.step_mfu."""
from benchlib.readers import step_mfu as read  # noqa: F401
