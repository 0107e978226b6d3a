"""CPU rehearsal of a whole run: the harness drives a chat-shaped cell end
to end at smoke size (set-up, open-loop window, reference check), the
real command refuses to run without a TPU, and no device metric comes
out of a CPU run."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

from conftest import REPO, run_json

ARGS = ["--workload", "smoke.chat", "--seed", "3000000019", "--seconds",
        "4"]


def test_chat_cell_end_to_end(smoke_root, capsys):
    rc, res, err = run_json(smoke_root, ARGS + ["--trace", "0"], capsys)
    assert rc == 0, err
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p95_s",
                                   "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_gap"]["value"] <= 0.2
    assert re.search(r"\b0 compiles inside the window", err)
    # the compared number is the last line of standard error
    assert err.strip().splitlines()[-1].startswith("check max_gap:")


def test_longgen_prelude_runs_until_memos_preempts(smoke_root, capsys):
    """The longgen-shaped path (memos on, a backlog, a prelude that runs
    until the first preemption) at smoke size: the window preempts and
    migrates pages both ways across the HBM boundary."""
    rc, res, err = run_json(smoke_root, ["--workload", "smoke.longgen",
                                         "--seed", "3000000023", "--seconds",
                                         "4", "--trace", "0"], capsys)
    assert rc == 0, err
    assert re.search(r"prelude [0-9.]+ s with [1-9]\d* preemptions", err)
    m = re.search(r"preempted (\d+),", err)
    assert m and int(m[1]) > 0
    m = re.search(r"\(to host (\d+), to HBM (\d+)\)", err)
    assert m and int(m[1]) > 0 and int(m[2]) > 0


def test_no_device_metric_from_a_cpu_run(smoke_root, capsys):
    rc, res, err = run_json(smoke_root, ARGS + ["--trace", "1"], capsys)
    assert rc == 0, err
    bench = json.loads((smoke_root / "BENCHMARK.json").read_text())
    device_sourced = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
    assert res["metrics"], "the span- and counter-read metrics are missing"
    assert not device_sourced & set(res["metrics"])
    assert "busy_s" not in res["device"] and "breakdown" not in res


# padded-work shares and the counters each divides
SHARES = {"decode.live_row_share.chat": ("serving.tokens_sampled",
                                         "serving.decode_row_slots"),
          "prefill.fill_share.chat": ("serving.prefill_tokens",
                                      "serving.prefill_slots"),
          "attention.page_fetch_share.chat": ("serving.attn_pages_fetched",
                                              "serving.attn_page_slots")}


def test_counter_shares_read_the_window_counters(smoke_root, capsys):
    """The padded-work shares, read from the counters the record took as
    the window closed, equal what the program's registry still holds
    after the run, where the readers used to read them."""
    from repro import obs
    rc, res, err = run_json(smoke_root, ARGS + ["--trace", "1"], capsys)
    assert rc == 0, err
    reg = {n: m.value for n, m in obs.get_registry().collect().items()
           if isinstance(m, obs.Counter)}
    assert reg["serving.dispatches"] > 0
    for name, (num, den) in SHARES.items():
        assert res["metrics"][name]["value"] == 100.0 * reg[num] / reg[den]


def _cli(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_real_command_needs_a_tpu():
    p = _cli(REPO, "--workload", "qwen3_4b.chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program
    to serve: the command fails and prints no result."""
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _cli(tmp_path, "--workload", "qwen3_4b.chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
