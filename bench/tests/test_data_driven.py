"""A cell and a metric are added by adding files: a traffic mix, a cell
file and a metric reader, with entries in BENCHMARK.json, and the
harness runs them without an edit to any file it already had."""
from __future__ import annotations

import hashlib
import json

from conftest import make_root, run_json


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
