"""``bench/breakdown.py``: the named scope of each op read from its
program's compiled HLO, device time split by program and scope, and TTFT
split by part; on hand-built inputs and on a traced smoke run on the CPU,
whose ops carry their module and instruction names."""
from __future__ import annotations

import importlib.util
import json
import types

import pytest

from conftest import BENCH
from repro.obs.trace import SpanEvent

_spec = importlib.util.spec_from_file_location("bench_breakdown",
                                               BENCH / "breakdown.py")
BD = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(BD)

HLO = """HloModule jit__fused_decode, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %exp.3 = f32[4]{0} exponential(f32[4]{0} %p), metadata={op_name="jit(_fused_decode)/while/body/mlp/exp" source_file="x.py"}
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.115 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_fused_decode)/while/body/attention/jit(paged_attention)/mul"}
  %slice_bitcast_fusion.7.remat = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_fused_decode)/while/body/closed_call/slice"}
  %copy.2 = f32[4]{0} copy(f32[4]{0} %a)
  ROOT %paged_attention.68 = f32[4]{0} custom-call(f32[4]{0} %fusion.115), custom_call_target="tpu_custom_call", metadata={op_name="jit(_fused_decode)/while/body/kv_append/squeeze;jit(_fused_decode)/while/body/attention/reshape"}
}
"""


def test_scope_of_an_op_name():
    assert BD.scope_of("jit(f)/while/body/closed_call/mlp/dot_general") \
        == "mlp"
    assert BD.scope_of("jit(f)/attention/jit(paged_attention)/exp") \
        == "attention"
    # fusion joins the names of what it fused: the first part decides
    assert BD.scope_of("jit(f)/kv_append/squeeze;jit(f)/attention/x") \
        == "kv_append"
    assert BD.scope_of("jit(f)/while/body/closed_call/slice") == "unscoped"
    assert BD.instr_name("%fusion.115 = bf16[64]{0} fusion(...)") \
        == "fusion.115"


def test_hlo_scopes_read_every_instruction():
    got = BD.hlo_scopes(HLO)
    assert got["fusion.115"] == "attention"
    assert got["exp.3"] == "mlp"
    assert got["slice_bitcast_fusion.7.remat"] == "unscoped"
    assert got["copy.2"] == "unscoped"              # no metadata
    assert got["paged_attention.68"] == "kv_append"


def test_by_scope_picks_the_variant_that_holds_an_execution():
    k8 = {"fusion.1": "mlp", "fusion.2": "attention"}
    k1 = {"fusion.1": "attention", "fusion.9": "head"}
    ops = [("jit__fused_decode", 0, "fusion.1", 3e9),
           ("jit__fused_decode", 0, "fusion.2", 1e9),
           ("jit__fused_decode", 1, "fusion.1", 2e9),
           ("jit__fused_decode", 1, "fusion.9", 1e9),
           ("jit__fused_decode", 1, "copy.4", 0.5e9),
           ("jit__core_plain", 2, "fusion.1", 4e9)]
    got = BD.by_scope(ops, {"jit__fused_decode": [k8, k1]})
    dec = got["jit__fused_decode"]
    assert dec["executions"] == 2 and dec["device_s"] == pytest.approx(7.5)
    assert dec["scopes"] == pytest.approx(
        {"mlp": 3.0, "attention": 3.0, "head": 1.0, "unmapped": 0.5})
    assert dec["ops"]["fusion [mlp]"] == pytest.approx(3.0)
    assert dec["ops"]["fusion [attention]"] == pytest.approx(3.0)
    # a program with no compiled HLO is all unmapped
    assert got["jit__core_plain"]["scopes"] == {"unmapped": 4.0}
    assert list(got) == ["jit__fused_decode", "jit__core_plain"]


def _served(rid, due, submit, first):
    return types.SimpleNamespace(rid=rid, due=due, req=types.SimpleNamespace(
        submit_ts=submit, first_token_ts=first))


def test_ttft_parts_follow_each_request_by_its_id():
    ms = 1_000_000
    spans = [SpanEvent("serve.prefill", "X", 1000 * ms, 100 * ms, 1,
                       {"rids": [0, 1], "tokens": 200}),
             SpanEvent("serve.prefill", "X", 2000 * ms, 50 * ms, 1,
                       {"rids": [2]}),
             SpanEvent("serve.dispatch", "X", 1200 * ms, 400 * ms, 1,
                       {"k": 8})]
    served = [_served(0, 0.5, 0.9, 1.1), _served(1, 0.8, 0.9, 1.1),
              _served(2, 1.5, 1.9, 2.05),
              _served(3, 1.9, 1.95, None),         # not prefilled yet
              _served(4, 0.1, 0.2, 0.4)]           # prefilled before
    got = BD.ttft_parts(served, spans)
    assert got["requests"] == 3
    assert got["due_to_submit"]["p50"] == pytest.approx(0.4)
    assert got["submit_to_prefill"]["p50"] == pytest.approx(0.1)
    assert got["prefill"]["p95"] == pytest.approx(0.1)
    assert got["ttft"]["p50"] == pytest.approx(0.55)
    assert got["due_to_prefill"]["p50"] == pytest.approx(0.5)
    assert BD.ttft_parts(served, spans[2:]) is None


def test_breakdown_of_a_traced_smoke_run(smoke_root, capsys, tmp_path):
    spec = importlib.util.spec_from_file_location(
        "bench_breakdown_smoke", smoke_root / "bench" / "breakdown.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = tmp_path / "breakdown.json"
    rc = mod.main(["--workload", "smoke.chat", "--seed", "3000000031",
                   "--seconds", "4", "--out", str(out)], allow_cpu=True)
    stdout, err = capsys.readouterr()
    assert rc == 0, err
    got = json.loads(out.read_text())
    assert got["result"]["correct"] is True
    progs = got["programs"]
    for prog in ("jit__fused_decode", "jit__core_plain"):
        r = progs[prog]
        assert r["executions"] > 0 and r["device_s"] > 0
        # nearly every op finds its instruction in the compiled HLO
        assert r["scopes"].get("unmapped", 0.0) < 0.05 * r["device_s"], r
        assert {"attention", "mlp", "kv_append", "head"} <= set(r["scopes"])
    assert "sysmon" in progs["jit__fused_decode"]["scopes"]
    t = got["ttft"]
    assert t["requests"] > 0
    assert 0 <= t["due_to_prefill"]["p50"] <= t["ttft"]["p50"]
    assert "program jit__fused_decode:" in stdout
    assert stdout.strip().splitlines()[-1].startswith("{")
