"""Pallas TPU kernels for the compute hot-spots (each with ops.py jit
wrapper and ref.py pure-jnp oracle; validated in interpret mode on CPU):

  flash_attention — train/prefill attention (causal/SWA/local-global, GQA)
  paged_attention — decode/prefill attention over the memos block-table
                    page pool, DMAing each row's live pages in place
  ssd_scan        — Mamba-2 SSD chunked scan with fused inter-chunk state
  page_gather     — migration-engine page pack/unpack (scatter-gather DMA)
  hotness_update  — fused SysMon pass (WD classify + history + predictor)
  wear_update     — NVM wear-counter scatter-add (telemetry subsystem)
"""
from . import (flash_attention, hotness_update, page_gather,
               paged_attention, ssd_scan, wear_update)

__all__ = ["flash_attention", "hotness_update", "page_gather",
           "paged_attention", "ssd_scan", "wear_update"]
