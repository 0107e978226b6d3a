"""The comparison fails where it should, at smoke size on the CPU: the
fp8 control put in the program's place, and the timed path broken
underneath the harness (a token altered where it is sampled; a decode
step that hands back its KV pool unchanged)."""
from __future__ import annotations

import importlib.util
import json

import jax.numpy as jnp

from conftest import run_json

ARGS = ["--workload", "smoke.chat", "--seed", "3000000029", "--seconds",
        "4", "--trace", "0"]


def test_fp8_control_fails_the_limit(smoke_root, capsys):
    """``bench/calibrate.py`` reads the control beside the program on the
    same served tokens."""
    spec = importlib.util.spec_from_file_location(
        "bench_calibrate_smoke", smoke_root / "bench" / "calibrate.py")
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    rc = cal.main(["--workload", "smoke.chat", "--seeds", "3000000029",
                   "--seconds", "4", "--control", "fp8"], allow_cpu=True)
    out, err = capsys.readouterr()
    assert rc == 0, err
    row = json.loads(out.strip().splitlines()[-1])
    assert row["correct"] is True, err
    assert row["control_fp8"] > row["checks"]["max_gap"]["limit"]


def _patch_fused(eng, change):
    orig = eng._get_fused

    def get(k):
        fn = orig(k)

        def broken(*args):
            out = fn(*args[:8], jnp.copy(args[8]), *args[9:])
            return change(out, args)
        return broken
    eng._get_fused = get


def test_altered_token_is_caught(smoke_root, capsys):
    def breaker(eng):
        vocab = eng.cfg.vocab
        _patch_fused(eng, lambda out, args: ((out[0] + 1) % vocab,)
                     + tuple(out[1:]))
    rc, res, err = run_json(smoke_root, ARGS, capsys, breaker=breaker)
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["max_gap"]["value"] > 1.0


def test_unchanged_state_is_caught(smoke_root, capsys):
    def breaker(eng):
        # the dispatch's KV writes are dropped: it returns the pool it got
        _patch_fused(eng, lambda out, args: tuple(out[:3]) + (args[8],)
                     + tuple(out[4:]))
    rc, res, err = run_json(smoke_root, ARGS, capsys, breaker=breaker)
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["max_gap"]["value"] > 0.2

