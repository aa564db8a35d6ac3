"""Resolve a cell from ``BENCHMARK.json`` into its data files and modules.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name:

- ``configs/<config>.json``: the configuration as it is run; its
  ``family`` names ``reference/<family>.py`` (the plain reference) and
  ``adapters/<family>.py`` (how the program is given it);
- ``traffic/<traffic>.json``: the parameters of the general generator;
- ``metrics/<metric>.py``: one reader per per-layer metric.  A metric
  split by the end-to-end metric it moves (``step_mfu.serve``,
  ``step_mfu.sat``) reads the same quantity in other cells, so where
  ``metrics/<metric>.py`` is absent its stem's ``metrics/<stem>.py``
  reads it.

So a new cell is new files and new entries, and no edit of these.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_module(path: Path):
    """Import one file by path (metric names hold dots)."""
    name = "perfbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # configs/<config>.json
    traffic: dict          # traffic/<traffic>.json
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def family(self) -> str:
        return self.config["family"]

    def reference(self):
        return load_module(BENCH_DIR / "reference" / f"{self.family}.py")

    def adapter(self):
        return load_module(BENCH_DIR / "adapters" / f"{self.family}.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(bench).read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    config.setdefault("name", w["config"])
    config.setdefault("source", cfg_entry["source"])
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
    )


def metric_reader(name: str) -> Path:
    """The reader of per-layer metric ``name``: its own file, or else
    the file of its stem, the name up to its first dot."""
    own = BENCH_DIR / "metrics" / f"{name}.py"
    return own if own.exists() else (
        BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py")


def read_per_layer(cell: Cell, ctx) -> dict:
    """Run each per-layer metric's reader over the traced run's
    context; a reader that finds nothing returns None and its metric is
    left out of the line."""
    out = {}
    for m in cell.per_layer:
        value = load_module(metric_reader(m["name"])).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def seed_stream(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use of the run's seed; any whole
    number, however large, is a valid seed."""
    key = [int(b) for b in stream.encode()]
    return np.random.default_rng(np.random.SeedSequence([seed, *key]))


def seed32(seed: int, stream: str) -> int:
    """A 31-bit seed for ``jax.random.key`` drawn from ``seed``."""
    return int(seed_stream(seed, stream).integers(0, 2 ** 31 - 1))
