"""Finds a cell's files by name: the workload entry in ``BENCHMARK.json``,
its configuration (``configs/<config>.json``), the configuration's model
module (``models/<reference>.py``), its traffic mix (``traffic/<mix>.json``)
and its cell settings (``cells/<workload>.json``: offered rate, tier
sizes and the limits of the correctness check).

Nothing here knows a model, a mix or a metric by name: a later cell
brings its own files and an entry in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from functools import cached_property
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    root: Path              # checkout root (holds BENCHMARK.json)
    bench: dict             # the whole BENCHMARK.json
    workload: dict          # this cell's entry of "workloads"
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<mix>.json
    cell: dict              # cells/<workload>.json

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"

    @cached_property
    def model(self):
        """The configuration's model module, ``models/<reference>.py``:
        one name, the configuration's ``reference``, picks both the model
        module and the plain reference (``references/<reference>.py``).
        The module gives the harness everything that depends on the kind
        of model:

        * ``make_params(cfg, seed)``: the parameter tree the program
          reads, drawn from the seed on the device in the served dtype;
          ``abstract_params(cfg)``: its shapes, drawing nothing;
        * ``padded_vocab(cfg)``: the width of the program's logits;
        * ``attention_work(rec, steps)``: the paged-attention kernel
          calls of ``steps`` as ``(layers, [(flops, bytes), ...])``, each
          of ``layers`` layers making every call (``costs.min_seconds``);
        * ``model_flops(rec, steps)``: the model FLOPs of the tokens
          ``steps`` processed.

        ``cfg`` is the program's ``ArchConfig``; ``rec`` the run's
        ``RunRecord``, whose ``counters`` hold what the program counted
        (the expert work a routed model did, say)."""
        return load_module(self.bench_dir / "models"
                           / f"{self.config['reference']}.py")

    def end_to_end(self) -> list[dict]:
        """End-to-end metrics this cell reports (``setup_s`` and those
        whose ``workloads`` list names it, or that have no list)."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        """Per-layer metrics whose ``workloads`` list names this cell, or
        that have no list and move an end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out


def load_cell(workload: str, root: Path | None = None) -> Cell:
    root = Path(root) if root is not None else BENCH_DIR.parent
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    wl = entries[0]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[wl["config"]]["file"]).read_text())
    bdir = root / "bench"
    traffic = json.loads((bdir / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    cell = json.loads((bdir / "cells" / f"{workload}.json").read_text())
    return Cell(root, bench, wl, cfg, traffic, cell)


def load_module(path: Path):
    """A benchmark file (a model, a reference, a metric reader) as a
    module of its own, by its path."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(path.parts[-2:]).removesuffix(".py")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file: its
    ``serve_as`` values, which restate the published keys in the
    program's names."""
    from repro.configs.base import ArchConfig
    return ArchConfig(name=config["name"], **config["serve_as"])
