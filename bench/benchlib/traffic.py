"""Open-loop traffic from a mix file and a seed.

The arrival processes and the clipped-lognormal lengths are copied from
``repro.qos.traces`` (seeded Poisson and bursty streams), so the
yardstick does not move when the program's generator does.

Every seed gets the same work: the mix's own ``shape_seed`` draws the
arrival times and the (prompt, output) lengths, in their order, and
``--seed`` draws the token ids (and, in the harness, the weights).  A
schedule that moves with the seed moves the work in the window: on
qwen3_4b.chat (one TPU v5e, 51 s windows, two sets of six seeds) a seed
that only rotated one fixed cycle of arrivals spread tokens/s by 3.7-4.6%,
the p95 of TPOT by 7.3-8.6% and that of TTFT by 10.6-28.8% across seeds,
while each seed read alike in both sets; the same gaps and sizes freely
permuted spread TTFT's p95 by 27%.  With the order fixed they spread
0.6%, 1.6-3.8% and 1.8-6.1% (40 s windows).

A mix file holds::

    process            poisson | bursty
    shape_seed         seed of the sizes and gaps (fixed per mix)
    prompt, output     {"mean", "sigma", "lo", "hi"} clipped lognormal
    max_pages_per_seq  block-table width the engine is built with
    backlog_batches    requests due at the start of the prelude, in
                       units of the configuration's max_batch
    prelude_until      "none" | "first_preemption"
    prelude_max_s      cap on that prelude
    check_requests     finished requests compared with the reference
    burst_size, burst_spread_s (bursty)

The offered rate is the cell's (``cells/<workload>.json``)."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class LengthDist:
    """Clipped-lognormal integer lengths."""
    mean: float
    sigma: float = 0.4
    lo: int = 1
    hi: int = 64

    def sample(self, rng: np.random.RandomState, n: int) -> np.ndarray:
        v = rng.lognormal(mean=float(np.log(self.mean)), sigma=self.sigma,
                          size=n)
        return np.clip(np.round(v).astype(np.int64), self.lo, self.hi)


def _poisson_times(rate: float, duration: float,
                   rng: np.random.RandomState) -> list[float]:
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= duration:
            return out
        out.append(t)


def _bursty_times(rate: float, burst_size: int, spread: float,
                  duration: float, rng: np.random.RandomState) -> list[float]:
    out = []
    for t0 in _poisson_times(rate / max(burst_size, 1), duration, rng):
        offs = np.sort(rng.uniform(0.0, spread, size=burst_size))
        out.extend(float(t0 + o) for o in offs if t0 + o < duration)
    return sorted(out)


@dataclass
class Arrival:
    t: float                 # seconds after the window opens (backlog: 0)
    prompt: list[int]
    max_new: int


def _dist(d: dict) -> LengthDist:
    return LengthDist(float(d["mean"]), float(d["sigma"]), int(d["lo"]),
                      int(d["hi"]))


def arrival_times(mix: dict, rate: float, seconds: float,
                  rng: np.random.RandomState) -> list[float]:
    proc = mix["process"]
    if proc == "poisson":
        return _poisson_times(rate, seconds, rng)
    if proc == "bursty":
        return _bursty_times(rate, int(mix["burst_size"]),
                             float(mix["burst_spread_s"]), seconds, rng)
    raise ValueError(f"unknown arrival process {proc!r}")


def schedule(mix: dict, rate: float, seconds: float, seed: int, vocab: int,
             n_backlog: int) -> tuple[list[Arrival], list[Arrival]]:
    """(backlog, arrivals): ``n_backlog`` requests due when the prelude
    starts, and the open-loop arrivals of a ``seconds`` window."""
    shape_rng = np.random.RandomState(int(mix["shape_seed"]))
    times = arrival_times(mix, rate, seconds, shape_rng)
    n = n_backlog + len(times)
    plens = _dist(mix["prompt"]).sample(shape_rng, n)
    olens = _dist(mix["output"]).sample(shape_rng, n)

    rng = np.random.default_rng(int(seed))
    reqs = [Arrival(0.0, rng.integers(0, vocab, int(plens[i])).tolist(),
                    int(olens[i])) for i in range(n)]
    for r, t in zip(reqs[n_backlog:], times):
        r.t = float(t)
    return reqs[:n_backlog], reqs[n_backlog:]


def warm_requests(seed: int, vocab: int, prompt_len: int,
                  decode_block: int) -> list[Arrival]:
    """Requests that take the engine through every dispatch shape the
    cell uses: the prefill bucket, and each power-of-two K up to
    ``decode_block`` as the generation budget runs down (2 * K tokens:
    one from prefill, then K, K/2, ..., 1)."""
    rng = np.random.default_rng([int(seed), 1])
    return [Arrival(0.0, rng.integers(0, vocab, prompt_len).tolist(),
                    2 * decode_block) for _ in range(2)]
