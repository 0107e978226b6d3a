"""The reader of ``attention.page_fetch_share.chat``: the program's page
counters as the window closed."""
from __future__ import annotations

import pytest

from benchlib.spec import load_module
from conftest import BENCH
from test_attribution import _hand_run


@pytest.fixture
def share():
    return load_module(BENCH / "metrics"
                       / "attention.page_fetch_share.chat.py").read


def test_reads_the_program_counters(share):
    rec = _hand_run(counters={"serving.dispatches": 2,
                              "serving.attn_pages_fetched": 72,
                              "serving.attn_page_slots": 576})
    assert share(rec) == pytest.approx(12.5)


def test_a_program_without_the_counters_gives_nothing(share):
    assert share(_hand_run(counters={"serving.dispatches": 2})) is None
