"""paged_attention_decode_roofline.chat: see benchlib.attribution.paged_attention_decode_roofline."""
from benchlib.attribution import paged_attention_decode_roofline as read  # noqa: F401
