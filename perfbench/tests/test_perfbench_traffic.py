"""The traffic generator, the files the harness finds by name, and the
command's refusal off the chip."""

import collections
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness.cell import load_module, metric_reader  # noqa: E402
from harness.traffic import schedule  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PER_LAYER = [m["name"] for m in SPEC["per_layer"]]
BIG_SEED = 2 ** 31 + 977


def mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = schedule(mix(name), BIG_SEED, 20.0, 1000, 1024)
    b = schedule(mix(name), BIG_SEED, 20.0, 1000, 1024)
    assert [(r.due, r.task, r.l_out) for r in a] == \
        [(r.due, r.task, r.l_out) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_offer_the_same_work_at_the_same_moments(name):
    m = mix(name)
    a = schedule(m, BIG_SEED, 20.0, 1000, 1024)
    b = schedule(m, 5, 20.0, 1000, 1024)
    assert [(r.due, r.task, len(r.prompt), r.l_out) for r in a] == \
        [(r.due, r.task, len(r.prompt), r.l_out) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    n = round(m["rate_rps"] * 20.0)
    assert sum(r.in_window for r in a) == n
    win = [r.due for r in a if r.in_window]
    assert m["lead_s"] <= min(win) and max(win) < m["lead_s"] + 20.0
    assert all(len(r.prompt) + r.l_out <= 1023 for r in a)
    assert all(0 < t < 1000 for r in a for t in r.prompt)
    tasks = collections.Counter(r.task for r in a if r.in_window)
    assert max(tasks.values()) - min(tasks.values()) <= 1


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_per_layer_metric_has_a_reader(name):
    path = metric_reader(name)
    assert path.exists() and path.parent == BENCH / "metrics"
    assert callable(load_module(path).read)


def test_a_split_metric_falls_back_to_its_stem():
    assert metric_reader("step_mfu.some_cell") == BENCH / "metrics" / \
        "step_mfu.py"
    assert metric_reader("prefill_chunk_ms") == BENCH / "metrics" / \
        "prefill_chunk_ms.py"


def test_command_refuses_a_host_with_no_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen7b.four-task", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr
