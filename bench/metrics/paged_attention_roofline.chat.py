"""paged_attention_roofline.chat: see benchlib.readers.paged_attention_roofline."""
from benchlib.readers import paged_attention_roofline as read  # noqa: F401
