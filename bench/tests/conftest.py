"""Fixtures for the benchmark's own tests (run on the CPU at smoke size):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``smoke_root`` copies ``bench/`` into a fresh checkout-shaped directory
and adds a smoke-size configuration and cells beside the real ones, so a
test drives the same harness and files the chip runs use, at widths the
CPU holds."""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE = {
    "name": "smoke", "source": "smoke() of bench/configs/qwen3_4b.json",
    "reduced": ["hidden_size"], "reference": "dense",
    "serve_as": {"family": "dense", "n_layers": 2, "d_model": 128,
                 "n_heads": 4, "n_kv_heads": 2, "d_head": 32, "d_ff": 256,
                 "vocab": 256, "qk_norm": True, "rope_theta": 1000000.0,
                 "norm_eps": 1e-06, "tie_embeddings": True},
    "engine": {"page_size": 16, "max_batch": 8, "decode_block": 8,
               "memos_interval": 8, "prefill_bucket": 256,
               "prefill_max_segments": 4},
}

SMOKE_LONGGEN = {
    "process": "poisson", "shape_seed": 7,
    "prompt": {"mean": 140, "sigma": 0.45, "lo": 64, "hi": 256},
    "output": {"mean": 120, "sigma": 0.15, "lo": 96, "hi": 160},
    "max_pages_per_seq": 28, "backlog_batches": 1.5,
    "prelude_until": "first_preemption", "prelude_max_s": 60,
    "check_requests": 3,
}

# smoke-size limits (the chip cells' own limits are set from chip runs)
CELLS = {
    "smoke.chat": {"rate_rps": 3.0, "hbm_pages": 200, "host_pages": 400,
                   "engine": {"memos_enabled": False},
                   "limits": {"max_gap": 0.2}},
    "smoke.longgen": {"rate_rps": 3.0, "hbm_pages": 60, "host_pages": 400,
                      "limits": {"max_gap": 0.2}},
}


def make_root(dst: Path) -> Path:
    """A checkout-shaped directory: ``bench/`` and a BENCHMARK.json whose
    cells are the smoke ones, with every per-layer metric of the real
    file re-pointed at the smoke cell of the same mix."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "bench" / "configs" / "smoke.json").write_text(json.dumps(SMOKE))
    (dst / "bench" / "traffic" / "smoke_longgen.json").write_text(
        json.dumps(SMOKE_LONGGEN))
    for name, c in CELLS.items():
        (dst / "bench" / "cells" / f"{name}.json").write_text(json.dumps(c))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "smoke", "source": SMOKE["source"],
                         "file": "bench/configs/smoke.json",
                         "reduced": ["hidden_size"], "why": "CPU tests"}]
    bench["workloads"] = [
        {"name": "smoke.chat", "config": "smoke", "traffic": "chat",
         "chips": 1, "why": "CPU rehearsal of the chat mix"},
        {"name": "smoke.longgen", "config": "smoke",
         "traffic": "smoke_longgen", "chips": 1,
         "why": "CPU rehearsal of a longgen-shaped mix that preempts"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            mix = m["workloads"][0].split(".", 1)[1]
            m["workloads"] = ["smoke.chat" if mix == "chat"
                              else "smoke.longgen"]
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dst


def load_run(root: Path):
    """``bench/run.py`` of ``root`` as a module."""
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(root)))}", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_json(root: Path, argv: list[str], capsys, **kw) -> tuple[int, dict,
                                                                 str]:
    """Run the harness in this process on the CPU; (exit code, result
    object or None, standard error)."""
    rc = load_run(root).main(argv, allow_cpu=True, **kw)
    out, err = capsys.readouterr()
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return rc, (json.loads(last) if last.startswith("{") else None), err


@pytest.fixture(scope="session")
def smoke_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("checkout"))
