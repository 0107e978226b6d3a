"""The dense model module gives what the harness computed before the kind
of model moved out of it: the same weights, bit for bit, from the same
seeds, and the same attention work, model FLOPs and weight bytes for
``qwen3_4b.chat``.  The expected values were recorded from the harness
as it stood before (``benchlib.weights.make_params``,
``benchlib.costs.attention_min_seconds`` and ``.model_flops``, and
``bench/aot.py``'s ``weight_bytes``)."""
from __future__ import annotations

import hashlib
import types

import jax
import numpy as np
import pytest

from benchlib import costs, spec, weights
from benchlib.peaks import PEAKS
from conftest import REPO, SMOKE

PARAMS_SHA256 = {
    7: "6c06048c991bfa16179a873b0f24f50485d5b7b0eafe0d6bdccaf5e976fcee88",
    2**33 + 5:
        "159b1f0e5a4a9f16556fd3c500ffaa7bd34c646057d121aba6a614fab03e845e",
}
# a prefill of three prompts, a multi-step decode of rows of different
# lengths and K, a step with both, and one long row
STEPS = [costs.StepWork(prefill=[140, 256, 64]),
         costs.StepWork(decode=[(140, 8), (300, 8), (17, 3)]),
         costs.StepWork(prefill=[200], decode=[(148, 4), (75, 1)]),
         costs.StepWork(decode=[(1000, 2)])]
ATTENTION_MIN_SECONDS = 0.0017525478681318684
MODEL_FLOPS = 5027202514944.0
WEIGHT_BYTES = 8044936192


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(a)
        h.update(f"{jax.tree_util.keystr(path)}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def qwen():
    cell = spec.load_cell("qwen3_4b.chat", REPO)
    return cell, spec.arch_config(cell.config)


@pytest.mark.parametrize("seed", sorted(PARAMS_SHA256))
def test_dense_draws_the_same_weights(seed):
    dense = spec.load_module(REPO / "bench" / "models" / "dense.py")
    params = dense.make_params(spec.arch_config(SMOKE), seed)
    assert _digest(params) == PARAMS_SHA256[seed]


def test_dense_counts_the_same_work(qwen):
    cell, cfg = qwen
    rec = types.SimpleNamespace(cfg=cfg, counters={})
    work = cell.model.attention_work(rec, STEPS)
    assert costs.min_seconds(work, PEAKS["TPU v5 lite"]) \
        == ATTENTION_MIN_SECONDS
    assert cell.model.model_flops(rec, STEPS) == MODEL_FLOPS


def test_dense_weight_bytes_as_aot_reports_them(qwen):
    cell, cfg = qwen
    assert weights.param_bytes(cell.model.abstract_params(cfg)) \
        == WEIGHT_BYTES
