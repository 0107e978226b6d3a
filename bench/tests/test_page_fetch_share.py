"""The reader of ``attention.page_fetch_share.chat``: the program's page
counters under the window's registry guard."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro import obs
from test_attribution import _hand_run


@pytest.fixture
def share():
    path = (Path(__file__).resolve().parents[1] / "metrics"
            / "attention.page_fetch_share.chat.py")
    spec = importlib.util.spec_from_file_location("page_fetch_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    obs.reset()
    yield mod.read
    obs.reset()


def test_reads_the_program_counters(share):
    reg = obs.get_registry()
    reg.counter("serving.dispatches").inc(2)       # as _hand_run's record
    reg.counter("serving.attn_pages_fetched").inc(72)
    reg.counter("serving.attn_page_slots").inc(576)
    assert share(_hand_run()) == pytest.approx(12.5)
    reg.counter("serving.dispatches").inc()        # past the window
    assert share(_hand_run()) is None


def test_a_program_without_the_counters_gives_nothing(share):
    obs.get_registry().counter("serving.dispatches").inc(2)
    assert share(_hand_run()) is None
