"""A cell, a metric and a kind of model are added by adding files: a
traffic mix, a cell file, a metric reader, a configuration with its
model module and reference, with entries in BENCHMARK.json, and the
harness runs them without an edit to any file it already had."""
from __future__ import annotations

import hashlib
import json
import shutil
import types
from pathlib import Path

import pytest

from benchlib import costs
from benchlib.spec import arch_config, load_module
from conftest import REPO, make_root, run_json


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_mix_and_metric_by_files_alone(tmp_path, capsys):
    root = make_root(tmp_path)
    before = _digest(root)
    b = root / "bench"
    (b / "traffic" / "dummy.json").write_text(json.dumps({
        "process": "bursty", "burst_size": 3, "burst_spread_s": 0.1,
        "shape_seed": 5,
        "prompt": {"mean": 40, "sigma": 0.3, "lo": 16, "hi": 64},
        "output": {"mean": 12, "sigma": 0.3, "lo": 8, "hi": 16},
        "max_pages_per_seq": 8, "backlog_batches": 0,
        "prelude_until": "none", "check_requests": 2}))
    (b / "cells" / "smoke.dummy.json").write_text(json.dumps({
        "rate_rps": 4.0, "hbm_pages": 64, "host_pages": 128,
        "limits": {"max_gap": 0.2}}))
    (b / "metrics" / "dummy.prefill_tokens.py").write_text(
        '"""Prompt tokens prefilled in the window."""\n\n\n'
        "def read(rec):\n"
        "    return float(sum(sum(s.prefill) for s in rec.steps)) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "smoke.dummy", "config": "smoke",
                               "traffic": "dummy", "chips": 1,
                               "why": "added by files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_p95_s":
            m["workloads"].append("smoke.dummy")
    bench["per_layer"].append({
        "name": "dummy.prefill_tokens", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "model step: prefill",
        "moves": "ttft_p95_s", "workloads": ["smoke.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    args = ["--workload", "smoke.dummy", "--seed", "77", "--seconds", "3"]
    rc, res, err = run_json(root, args + ["--trace", "1"], capsys)
    assert rc == 0, err
    assert res["metrics"]["dummy.prefill_tokens"]["value"] > 0
    assert res["correct"] is True, err
    rc, res, err = run_json(root, args + ["--trace", "0"], capsys)
    assert rc == 0, err
    assert {"tokens_per_s", "ttft_p95_s", "setup_s"} <= set(res["metrics"])
    after = _digest(root)
    assert all(after[k] == v for k, v in before.items())


TOY = Path(__file__).resolve().parent / "toy_moe"
# a routed configuration: with top_k equal to the expert count every
# expert sees every token, so no routing decision can differ between the
# served bf16 and the float32 reference
TOY_MOE = {
    "name": "toy_moe", "source": "bench/tests/toy_moe",
    "reduced": [], "reference": "toy_moe",
    "serve_as": {"family": "moe", "n_layers": 2, "d_model": 128,
                 "n_heads": 4, "n_kv_heads": 2, "d_head": 32, "d_ff": 64,
                 "n_experts": 4, "top_k": 4, "expert_d_ff": 64,
                 "vocab": 256, "qk_norm": True, "rope_theta": 1000000.0,
                 "norm_eps": 1e-06, "tie_embeddings": True},
    "engine": {"page_size": 16, "max_batch": 8, "decode_block": 8,
               "memos_interval": 8, "prefill_bucket": 256,
               "prefill_max_segments": 4},
}
# the flops metric also leaves the window's steps beside BENCHMARK.json
FLOPS_READER = '''"""The model module's FLOPs of the window's steps."""
import json
from pathlib import Path


def read(rec):
    (Path(__file__).resolve().parents[2] / "steps.json").write_text(
        json.dumps([[s.prefill, s.decode] for s in rec.steps]))
    return rec.model.model_flops(rec, rec.steps)
'''


def _hand_flops(steps, n_experts=4, top_k=4) -> float:
    """The toy's layers, counted by hand: attention, the router over
    ``n_experts`` and the ``top_k`` experts each token passes through."""
    d, L, hq, hkv, dh, f = 128, 2, 4, 2, 32, 64
    attention = d * dh * (hq + 2 * hkv) + hq * dh * d
    experts = top_k * 3 * d * f
    router, norms = d * n_experts, 2 * d
    per_token = 2 * L * (attention + experts + router + norms)
    per_context = 4 * hq * dh * L
    total = 0.0
    for prefill, decode in steps:
        for n in prefill:
            total += per_token * n + per_context * n * (n + 1) / 2
        for p, k in decode:
            total += per_token * k + per_context * sum(
                p + 1 + j for j in range(k))
    return total


def test_routed_flops_count_the_chosen_experts(tmp_path):
    """With ``top_k`` under the expert count the toy charges each token
    the experts it is routed to, not all of them."""
    shutil.copy(REPO / "bench" / "models" / "dense.py", tmp_path)
    shutil.copy(TOY / "model.py", tmp_path / "toy_moe.py")
    toy = load_module(tmp_path / "toy_moe.py")
    cfg = arch_config({**TOY_MOE, "serve_as": {
        **TOY_MOE["serve_as"], "n_experts": 16, "top_k": 4}})
    steps = [[[140, 64], []], [[], [[140, 8], [17, 3]]],
             [[200], [[148, 4], [75, 1]]]]
    work = [costs.StepWork(prefill=p, decode=[tuple(r) for r in d])
            for p, d in steps]
    flops = toy.model_flops(types.SimpleNamespace(cfg=cfg, counters={}),
                            work)
    assert flops == pytest.approx(
        _hand_flops(steps, n_experts=16, top_k=4), rel=1e-12)
    assert flops < _hand_flops(steps, n_experts=16, top_k=16)


def test_new_kind_of_model_by_files_alone(tmp_path, capsys):
    """A routed model: its model module, its reference, its configuration
    and cell, and entries in BENCHMARK.json, with no edit to a file the
    harness had."""
    root = make_root(tmp_path)
    before = _digest(root)
    b = root / "bench"
    shutil.copy(TOY / "model.py", b / "models" / "toy_moe.py")
    shutil.copy(TOY / "reference.py", b / "references" / "toy_moe.py")
    (b / "configs" / "toy_moe.json").write_text(json.dumps(TOY_MOE))
    (b / "cells" / "toy_moe.chat.json").write_text(json.dumps({
        "rate_rps": 3.0, "hbm_pages": 200, "host_pages": 400,
        "engine": {"memos_enabled": False}, "limits": {"max_gap": 0.2}}))
    (b / "metrics" / "toy_moe.flops.py").write_text(FLOPS_READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy_moe", "source": TOY_MOE["source"],
                             "file": "bench/configs/toy_moe.json",
                             "reduced": [], "why": "a routed model"})
    bench["workloads"].append({"name": "toy_moe.chat", "config": "toy_moe",
                               "traffic": "chat", "chips": 1,
                               "why": "added by files alone"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("toy_moe.chat")
    bench["per_layer"].append({
        "name": "toy_moe.flops", "unit": "FLOP", "better": "higher",
        "source": "program_counter", "layer": "whole step",
        "moves": "tokens_per_s", "workloads": ["toy_moe.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    args = ["--workload", "toy_moe.chat", "--seed", "3000000041",
            "--seconds", "3"]
    rc, res, err = run_json(root, args + ["--trace", "0"], capsys)
    assert rc == 0, err
    assert res["correct"] is True, err
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    rc, res, err = run_json(root, args + ["--trace", "1"], capsys)
    assert rc == 0, err
    assert res["correct"] is True, err
    steps = json.loads((root / "steps.json").read_text())
    assert any(p for p, _ in steps) and any(d for _, d in steps)
    assert res["metrics"]["toy_moe.flops"]["value"] == pytest.approx(
        _hand_flops(steps), rel=1e-12)
    after = _digest(root)
    assert all(after[k] == v for k, v in before.items())
