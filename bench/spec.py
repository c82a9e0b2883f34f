"""Find a cell's pieces by name: nothing here names a cell.

``BENCHMARK.json`` (at the repository root) names each cell's
configuration and traffic mix. Then, under this directory:

- ``configs/<config>.json``: the configuration as run (the file the
  benchmark names), with the registry ``arch`` and its ``reference``;
- ``reference/<reference>.py``: its plain reference, which also holds
  every fact of the model's layer layout (below);
- ``traffic/<mix>.json``: the traffic mix and serving settings, whose
  ``kind`` names the generator ``traffic/<kind>.py``;
- ``limits/<cell>.json``: the limits of the correctness check;
- ``metrics/<metric>.py``: one reader per per-layer metric.

A new cell, configuration, mix or metric is a new file, never an edit.

A configuration file holds the source's ``config`` keys (Hugging Face
names), ``arch`` (the program's registry entry), ``reference``,
``reduced`` (the keys cut from the source), ``published`` (each cut key's
source value) and, optionally, ``assumed``. Its reference module provides
(``REFERENCE_API``):

- ``dims(cfg)``: the shape facts, hashable (jit takes them as static),
  with at least ``vocab`` and ``layers``; it reads the whole file, so a
  router can be as wide as the ``published`` expert count while the held
  count is the cut one;
- ``leaves(dims)``: the weight tree, ``{"top": {name: (shape, kind)},
  "stacks": {stack: (depth, {name: (shape, kind)})}}``, kinds from
  ``bench/weights.py``; it has to match the program's parameter tree;
- ``key_map``: configuration-file key -> the program's ``ModelConfig``
  field, and ``program_facts(dims)``: ``ModelConfig`` attribute -> the
  value the file implies (both checked by ``serve.program_config``);
- ``cache_entries(dims)``: the names of the program's cache leaves the
  check compares, each (layers, rows, positions, ...);
- ``sites(dims)``: site -> (input width, the layers it spans); the plan's
  mask of a site is (those layers, width); ``expected_mask(dims, site,
  importance, budget)``, optionally, where selection is not a top-k;
- ``kernel_calls(dims)``: kernel -> its calls in one decode step, in
  proportion; a call is a list of (site, output width) per matrix;
- ``flops_per_token(dims, context)``: one decode token with no selection;
- ``forward(dims, seed, tokens, logit_pos, masks_at=None, imp_pos=None,
  fp8=False, on_cache=None)``: float32 logits and each site's importance
  (site -> (its layers, positions, width)); ``on_cache(layer, entries)``
  gets each layer's cache entries by the names above.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    cfg: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path

    @property
    def engine(self) -> dict:
        return self.traffic["engine"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, repo_root: Path = REPO_ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bm = json.loads((repo_root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in bm["configs"]}
    cfg = json.loads((repo_root / cfgs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench_dir / "limits" / f"{workload}.json").read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], cfg=cfg, traffic=traffic, limits=limits,
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir,
    )


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_dyn_" + re.sub(r"\W", "_", str(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def traffic_source(cell: Cell, seed: int, vocab: int, seconds: float):
    gen = load_module(cell.bench_dir / "traffic" / f"{cell.traffic['kind']}.py")
    return gen.make_source(cell.traffic, seed, vocab, seconds)


REFERENCE_API = ("dims", "leaves", "key_map", "program_facts", "cache_entries", "sites",
                 "kernel_calls", "flops_per_token", "forward")


def reference_module(cell: Cell) -> ModuleType:
    ref = load_module(cell.bench_dir / "reference" / f"{cell.cfg['reference']}.py")
    missing = [name for name in REFERENCE_API if not hasattr(ref, name)]
    if missing:
        raise AttributeError(f"reference {cell.cfg['reference']!r} lacks {missing}")
    return ref


def metric_readers(cell: Cell) -> Dict[str, ModuleType]:
    return {m["name"]: load_module(cell.bench_dir / "metrics" / f"{m['name']}.py")
            for m in cell.per_layer}
