"""attention.page_fetch_share.chat: KV pages the paged-attention kernel
fetched, as a share of the (padded row, table column) pairs a walk of
every row's whole block table visits, over the single-pool decode and
prefill calls of the window (all layers).  Read from the program's
counters ``serving.attn_pages_fetched`` and ``serving.attn_page_slots``
as the window closed; a program without them gives nothing."""
from benchlib.attribution import counter_share


def read(rec):
    return counter_share(rec, "serving.attn_pages_fetched",
                         "serving.attn_page_slots")
