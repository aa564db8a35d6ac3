"""The comparison that decides ``correct``, at a size a test run holds.

A tiny Qwen2-family cell goes through the whole of a run on the CPU
(set-up, warm-up, an open-loop window, the reference check), with only
the look for a chip skipped.  Served as it is, it is correct; its
lower-precision control (the reference in fp8) reads over the limit;
and with a token altered where the engine produces it, ``correct``
comes out false.
"""

import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from harness import check, serve  # noqa: E402
from harness.cell import Cell  # noqa: E402

LIMIT = 1e-3    # the CPU computes float32 products exactly in float32


def tiny_cell() -> Cell:
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    name = "qwen7b.four-task"
    cfg = json.loads((BENCH / "configs" / "qwen7b.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
               initializer_range=0.1,
               engine={"n_slots": 4, "max_len": 64, "page_size": 8,
                       "chunk_size": 16, "decode_block": 4},
               check={"max_logit_gap": LIMIT, "sample_tokens": 150})
    mix = json.loads((BENCH / "traffic" / "four-task.json").read_text())
    mix.update(rate_rps=4.0, lead_s=1.0, drain_cap_s=10.0)
    return Cell(name=name, chips=1, config=cfg, traffic=mix,
                end_to_end=[m for m in spec["end_to_end"]
                            if name in m.get("workloads", [name])],
                per_layer=[])


def test_served_run_is_correct_and_its_fp8_control_is_not(monkeypatch):
    cell = tiny_cell()
    seen = {}
    gaps = check.gaps

    def gaps_with_control(cell_, weights, requests, **kw):
        seen.update(gaps(cell_, weights, requests, control=True))
        return gaps(cell_, weights, requests, **kw)

    monkeypatch.setattr(check, "gaps", gaps_with_control)
    out = run.run_cell(cell, 2 ** 31 + 5, 3.0, False, time.perf_counter())
    assert out["correct"] is True
    assert out["attempted"] == 12 and out["failed"] == 0
    assert set(out["metrics"]) == {"tpot_p90_s", "slo_attainment",
                                   "setup_s"}
    assert list(out)[-1] == "check"
    assert out["check"]["max_logit_gap"]["limit"] == LIMIT
    assert seen["positions"] >= 150
    assert seen["max_logit_gap"] <= LIMIT < seen["control_max_logit_gap"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving.engine import InferenceEngine

    step = InferenceEngine.step

    def altered_step(self):
        info = step(self)
        for rid, tok, _ in info.get("token_events", []):
            r = next((r for r in self.finished + list(self.active.values())
                      if r.rid == rid), None)
            if r is not None and r.generated and r.generated[-1] == tok:
                r.generated[-1] = (tok + 1) % self.model.cfg.vocab_size
        return info

    monkeypatch.setattr(InferenceEngine, "step", altered_step)
    out = run.run_cell(tiny_cell(), 11, 3.0, False, time.perf_counter())
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > LIMIT


def test_weights_reach_the_program_as_the_reference_makes_them():
    """The program's tree holds the reference's weights, norms shifted
    by the program's ``1 + w`` convention."""
    import numpy as np

    cell = tiny_cell()
    ours = serve.make_weights(cell, 3, program=False)
    theirs = serve.make_weights(cell, 3, program=True)
    seg = theirs["segments"][0]
    np.testing.assert_array_equal(seg["attn"]["wq"], ours["wq"])
    np.testing.assert_array_equal(seg["ffn"]["w_down"], ours["w_down"])
    np.testing.assert_array_equal(1.0 + seg["ln1"], ours["ln1"])
    np.testing.assert_array_equal(theirs["head"], ours["head"])


def test_the_seed_checkpoint_is_counted_and_kept_from_disk(tmp_path,
                                                           monkeypatch):
    import tempfile

    import repro.serving.weights as weights

    save = weights.save_checkpoint
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    cluster, skipped = serve.build_cluster(tiny_cell(), 3)
    assert skipped == cluster.weights.nbytes > 0
    assert not [p for p in tmp_path.rglob("*") if p.is_file()]
    assert weights.save_checkpoint is save

