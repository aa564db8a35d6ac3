"""Program spans (``repro.tracing``): the span tree a served run writes
into the profiler trace, and nothing made or computed without one."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.serving.cluster import Cluster, ClusterConfig
from repro.serving.engine import EngineConfig
from repro.serving.session import ServingSession

PHASES = ["hfx.engine.plan", "hfx.engine.launch", "hfx.engine.wait",
          "hfx.engine.commit"]


def _workload(rid0: int) -> list:
    rng = np.random.default_rng(5)
    out = []
    for i in range(6):
        n = int(rng.integers(6, 30))   # one or two 16-token chunks
        out.append(Request(rid=rid0 + i, task="gsm8k", l_in=n,
                           prompt=rng.integers(1, 256, n).astype(np.int32),
                           l_out=int(rng.integers(2, 9)),
                           ttft_slo=5.0, tpot_slo=1.0))
    out[-1].l_out = 14   # decodes alone at the end: fused blocks
    return out


def _serve(session, reqs) -> list:
    handles = [session.submit_request(r) for r in reqs]
    session.drain()
    return [list(h.request.generated) for h in handles]


class _Raises:
    """Stands in for ``TraceAnnotation`` with no profiler running: any
    span object made would raise."""

    @staticmethod
    def is_enabled():
        return False

    def __init__(self, *a, **k):
        raise AssertionError("a span object was made with no profiler")


def _spans(trace_dir) -> list:
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hfx."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One smoke cluster: a round untraced, the same prompts traced,
    and the same again with span objects forbidden."""
    session = ServingSession(Cluster(ClusterConfig(
        model=get_smoke_config("qwen7b"), backend="engine", n_workers=1,
        policy="hyperflexis", seed=0,
        engine=EngineConfig.smoke(decode_block=4))), admission="none")
    plain = _serve(session, _workload(0))
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        traced = _serve(session, _workload(100))
    finally:
        jax.profiler.stop_trace()
    mp = pytest.MonkeyPatch()
    mp.setattr(tracing, "TraceAnnotation", _Raises)
    try:
        forbidden = _serve(session, _workload(200))
    finally:
        mp.undo()
    session.close()
    return {"plain": plain, "traced": traced, "forbidden": forbidden,
            "spans": _spans(trace_dir)}


def _inside(spans, outer) -> list:
    return [s for s in spans if outer[1] <= s[1] and s[2] <= outer[2]
            and s is not outer]


def test_served_tokens_do_not_depend_on_tracing(served):
    assert served["traced"] == served["plain"] == served["forbidden"]
    assert all(served["plain"])


def test_every_dispatching_step_runs_plan_launch_wait_commit(served):
    spans = served["spans"]
    steps = [s for s in spans if s[0] == "hfx.engine.step"
             and s[3].get("kind") in ("prefill_chunk", "decode")]
    assert len(steps) >= 6
    kinds = {s[3]["kind"] for s in steps}
    assert kinds == {"prefill_chunk", "decode"}
    for st in steps:
        assert {"kind", "k", "step", "tokens"} <= set(st[3])
        phases = [s[0] for s in _inside(spans, st) if s[0] in PHASES]
        assert phases == PHASES, (st, phases)
    assert max(s[3]["k"] for s in steps) > 1   # a fused block ran


def test_prefill_chunks_carry_rows_and_live_rows(served):
    """A prefill chunk's program runs ``prefill_batch`` rows, of which
    ``live_rows`` carry prompt tokens; decode steps carry neither."""
    eng = EngineConfig.smoke()
    steps = [s[3] for s in served["spans"] if s[0] == "hfx.engine.step"]
    chunks = [a for a in steps if a.get("kind") == "prefill_chunk"]
    assert chunks
    for a in chunks:
        assert a["rows"] == eng.prefill_batch, a
        assert 1 <= a["live_rows"] <= a["rows"], a
        assert a["tokens"] <= a["rows"] * eng.chunk_size, a
    assert max(a["live_rows"] for a in chunks) == eng.prefill_batch
    assert not any({"rows", "live_rows"} & set(a) for a in steps
                   if a.get("kind") == "decode")


def test_a_model_without_experts_carries_no_expert_load(served):
    steps = [s for s in served["spans"] if s[0] == "hfx.engine.step"]
    assert steps
    assert not any({"expert_tokens", "experts_hit"} & set(s[3])
                   for s in steps)


def test_first_chunks_follow_one_submit_each(served):
    spans = served["spans"]
    submits = [s for s in spans if s[0] == "hfx.session.submit"]
    assert sorted(s[3]["rid"] for s in submits) == list(range(100, 106))
    assert all(s[3]["admitted"] == 1 for s in submits)
    firsts = []
    for st in spans:
        if st[0] == "hfx.engine.step" and "first_rids" in st[3]:
            for rid in str(st[3]["first_rids"]).split(";"):
                before = [s for s in submits
                          if s[3]["rid"] == int(rid) and s[2] <= st[1]]
                assert len(before) == 1, (rid, st)
                firsts.append(int(rid))
    assert sorted(firsts) == list(range(100, 106))


def test_step_links_each_worker_step_to_its_step_done(served):
    spans = served["spans"]
    events = [s for s in spans if s[0] == "hfx.cluster.event"]
    starts = [s for s in events if s[3].get("kind") == "worker_step"
              and "step" in s[3]]
    dones = {s[3]["step"]: s for s in events
             if s[3].get("kind") == "step_done"}
    assert starts and len(dones) == len(starts)
    for ws in starts:
        (eng,) = [s for s in _inside(spans, ws) if s[0] == "hfx.engine.step"]
        assert eng[3]["step"] == ws[3]["step"]
        assert dones[ws[3]["step"]][1] >= ws[2]
    # the other control-plane spans are there too, with their arguments
    names = {s[0]: s[3] for s in spans}
    assert "tokens" in names["hfx.cluster.stream"]
    assert {"queued", "placed"} <= set(names["hfx.dispatch.pass"])
    assert "pages" in names["hfx.kv.table"]


def test_no_profiler_no_span_object_and_no_argument(monkeypatch):
    def boom():
        raise AssertionError("argument computed with no profiler")

    monkeypatch.setattr(tracing, "TraceAnnotation", _Raises)
    with tracing.span("hfx.test", arg=boom) as sp:
        tracing.annotate(sp, arg=boom)
    assert sp is None


def test_callable_arguments_are_computed_while_tracing(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("hfx.test", a=lambda: 3, none=None) as sp:
            tracing.annotate(sp, b=lambda: "1;2")
    finally:
        jax.profiler.stop_trace()
    (span,) = [s for s in _spans(tmp_path) if s[0] == "hfx.test"]
    assert span[3] == {"a": 3, "b": "1;2"}
