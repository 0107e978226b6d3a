"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them (and, where the cell preempted any, some whose pages
left HBM and came back), is run through the plain float32 reference of
the configuration (``references/<name>.py``), teacher-forced on each
prompt and its served tokens.  For each served token the number read is
the gap by which its reference logit lies below the reference's best at
that position (0 where it is the reference's argmax).  The number
compared with the cell's limit is ``max_gap``, the widest such gap over
every sampled token: the first tokens, sampled by the prefill dispatch
from its last-position logits, and every later one, sampled by the fused
decode through the paged KV (for moved requests, through pages demoted
to the host and promoted back).  A bf16 engine leaves small gaps where
the reference's top two logits nearly tie; a wrong token, or logits
computed from wrong KV, leaves gaps of whole logits.

With ``control`` (``int8``, ``fp8``), the same reference with its weights
stored in that type (a precision below the served bf16) is put in the
program's place and the same number is read for the tokens it puts
first.
"""
from __future__ import annotations

import time

import numpy as np

from .spec import load_module


def sample(keep: list, n: int, seed: int) -> list:
    """``n`` finished requests: the longest, then up to half the rest from
    those that were preempted, then others, each drawn from the seed."""
    if not keep:
        return []
    rng = np.random.default_rng([int(seed), 2])
    longest = max(keep, key=lambda k: (len(k[1]) + len(k[2]), -k[0]))
    chosen = [longest]
    moved = [k for k in keep if k[3] and k is not longest]
    for i in rng.permutation(len(moved))[:max(n - 1, 0) // 2 or 1]:
        if len(chosen) < n:
            chosen.append(moved[i])
    rest = [k for k in keep if all(k is not c for c in chosen)]
    for i in rng.permutation(len(rest))[:n - len(chosen)]:
        chosen.append(rest[i])
    return chosen


def compare(cell, params, keep: list, seed: int, *,
            control: tuple[str, ...] = ()) -> tuple[dict, list[str]]:
    """(checks, lines): each compared number with its limit, and what to
    print before the result.  ``keep`` holds (rid, prompt, served tokens,
    moved) of every request the window finished."""
    mod = load_module(cell.bench_dir / "references"
                      / f"{cell.config['reference']}.py")
    sizes = cell.config["serve_as"]
    T = cell.traffic["max_pages_per_seq"] * cell.config["engine"]["page_size"]
    chosen = sample(keep, int(cell.traffic["check_requests"]), seed)
    lines = []
    if not chosen:
        return {}, ["check: the window finished no request to compare"]
    t0 = time.perf_counter()
    ref = mod.Reference(sizes, params, T)
    first, rest = [], []
    for rid, prompt, served, moved in chosen:
        g, _ = mod.gaps(ref, prompt, served)
        first.append(float(g[0]))
        rest.extend(float(x) for x in g[1:])
    checks = {"max_gap": {"value": max(first + rest),
                          "limit": cell.cell["limits"]["max_gap"]}}
    n_tok = len(first) + len(rest)
    lines.append(
        f"check: {len(chosen)} requests ({sum(k[3] for k in chosen)} moved "
        f"across the HBM boundary), {n_tok} served tokens, longest "
        f"{len(chosen[0][1])}+{len(chosen[0][2])}; reference "
        f"{time.perf_counter() - t0:.3f} s")
    lines.append(
        "check gaps: first-token max "
        f"{max(first):.6f}, decode max "
        f"{(max(rest) if rest else float('nan')):.6f}, decode p99 "
        f"{(np.percentile(rest, 99) if rest else float('nan')):.6f}; "
        f"tokens off the reference argmax "
        f"{sum(x > 0 for x in first + rest)}/{n_tok}")
    for quant in control:
        ctl = mod.Reference(sizes, params, T, quant=quant)
        c_first, c_rest = [], []
        for rid, prompt, served, moved in chosen:
            _, cg = mod.gaps(ref, prompt, served, ctl)
            c_first.append(float(cg[0]))
            c_rest.extend(float(x) for x in cg[1:])
        lines.append(
            f"control {quant}: max_gap {max(c_first + c_rest)!r} (first "
            f"tokens {max(c_first)!r}); tokens off "
            f"the reference argmax {sum(x > 0 for x in c_first + c_rest)}/"
            f"{len(c_first) + len(c_rest)}")
    return checks, lines
