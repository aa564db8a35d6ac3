"""chip_smoke.py rehearsed on CPU through its own functions, at the
reduced qwen7b size, and its refusal to run anywhere but on a TPU.

On CPU the served path takes the jnp branch of the kernel decision and
the paged kernel is checked in interpret mode; on a chip the same
functions run compiled at the published widths.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache
from repro.serving.engine import EngineConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

SMOKE = get_smoke_config("qwen7b")
# the chip's engine shape scaled down with the model: 8 slots, 16-token
# pages, fused 8-token decode blocks, several chunks per prompt
ENGINE = EngineConfig(n_slots=8, max_len=256, page_size=16, chunk_size=64,
                      decode_block=8)
TRAFFIC = dict(chip_smoke.TRAFFIC, prompt=(16, 160), out=(8, 16))


@pytest.fixture(scope="module")
def served():
    traffic = chip_smoke.make_traffic(0, SMOKE.vocab_size, **TRAFFIC)
    return chip_smoke.serve(SMOKE, ENGINE, traffic, seed=0)


def test_serve_phase_finishes_every_request(served):
    rep = chip_smoke.serve_report(served)
    assert rep["n_requests"] == TRAFFIC["n"]
    assert rep["n_failed"] == rep["n_rejected"] == 0
    assert rep["n_tokens"] == sum(r.l_out for r in served.requests)
    assert rep["n_dispatches"] > 0 and rep["decode_block_hist"]
    assert {r.task for r in served.requests} == set(TRAFFIC["tasks"])
    assert all(TRAFFIC["prompt"][0] <= r.l_in <= TRAFFIC["prompt"][1]
               for r in served.requests)


def test_correctness_phase_matches_reference(served):
    eng = served.cluster.workers[0].engine
    out = chip_smoke.check_tokens(eng.model, eng.params, served.requests)
    assert out["positions"] == sum(r.l_out for r in served.requests)
    assert out["mismatched"] == []
    assert 2 * out["checked"] >= out["positions"]
    # on CPU both precisions are plain float32
    assert out["max_drift_default_vs_highest"] < 1e-4


def test_correctness_phase_catches_a_wrong_token(served):
    """The check has teeth: a served token flipped where the reference
    is confident fails it."""
    eng = served.cluster.workers[0].engine
    r = served.requests[0]
    good = list(r.generated)
    try:
        r.generated = good[:-1] + [(good[-1] + 1) % SMOKE.vocab_size]
        with pytest.raises(AssertionError, match="disagree"):
            chip_smoke.check_tokens(eng.model, eng.params, [r], frac=0.0)
    finally:
        r.generated = good


def test_paged_kernel_phase_matches_oracle():
    out = chip_smoke.check_paged_kernel(SMOKE, ENGINE, seed=0,
                                        interpret=True)
    assert out["max_abs_err"] <= out["rtol"] * out["ref_max_abs"]


def test_weights_report_splits_vocabulary():
    from repro.models import build_model

    params = build_model(SMOKE).init(jax.random.key(0))
    rep = chip_smoke.weights_report(params, SMOKE.n_layers, 32)
    assert 0 < rep["embed_head_bytes"] < rep["weight_bytes"]
    assert rep["embed_head_share"] > rep["embed_head_share_at_full_depth"]


_PD_REHEARSAL = """
import chip_smoke, json
from repro.configs import get_smoke_config
from repro.serving.engine import EngineConfig
cfg = get_smoke_config("qwen7b")
eng = EngineConfig(n_slots=8, max_len=256, page_size=16, chunk_size=64,
                   decode_block=8)
traffic = chip_smoke.make_traffic(0, cfg.vocab_size, **{traffic!r})
print(json.dumps(chip_smoke.pd_across_chips(cfg, eng, traffic, 0)))
"""


def test_pd_across_devices_rehearsal():
    """The ``--chips 4`` path on four virtual CPU devices: P/D replicas
    on devices 0 and 1 (params and page pools), a d2d provision onto
    device 2, and tokens equal to the one-replica collocated run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, "-c", _PD_REHEARSAL.format(traffic=TRAFFIC)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["tokens_equal"]
    assert res["replica_devices"] == ["TFRT_CPU_0", "TFRT_CPU_1"]
    assert res["d2d_device"] == "TFRT_CPU_2"


def test_script_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_compile_cache_placement(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache sits
    at <checkout>/.jax_cache — never a temp, pid or time-derived path."""
    saved = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert compile_cache.enable_compile_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == "/some/where"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
