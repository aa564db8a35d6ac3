"""Real JAX inference engine: continuous batching over an actual model.

This is the execution plane the simulator abstracts: jitted step
functions, KV caches, greedy sampling, and the paper's SLO-aware
admission (Eq. 5 token budget) at the engine boundary.  It doubles as
the latency profiler — measured step times feed FittedLatencyModel
exactly like the paper's request profiler (Appendix A).

Requests are unified :class:`repro.core.request.Request` objects, so
the engine can be driven standalone (``submit``/``step``/``run_until_done``)
or cluster-backed through
:class:`repro.serving.backend.EngineWorker` — the same control plane
that schedules the simulator.

Two execution planes:

- **Paged / chunked (default)**: attention K/V lives in a shared pool
  of fixed-size pages (``PagedKVManager``); prompts prefill in chunks
  sized by the Eq. 5 token budget, and the engine alternates one
  prefill chunk with one decode iteration whenever both have work — so
  a long prompt never stalls in-flight decodes for more than one
  bounded chunk (the head-of-line blocking §5.1 schedules around).
  Prefill chunks and decode share ``Model.chunk_step``: a decode
  block scans it at chunk length 1 over every lane, and a chunk runs
  only its prefilling rows where no state is slot-resident.

- **Slot-based (fallback)**: monolithic full-prompt prefill into
  contiguous per-slot rows; kept for architectures the chunked plane
  doesn't cover (sliding-window rings, encoder frontends).

P/D disaggregation runs on the paged plane: with ``park_on_prefill``
set (a prefill-role engine), requests whose prompt completes *park* —
their pages stay resident but they never join the decode batch — until
``export_kv`` materializes the cache + generation state into a
:class:`~repro.serving.kv_manager.KVPayload` and ``import_kv`` installs
it on the decode engine, which continues generating token-identically
(greedy decode over the same cache contents).


Designed for reduced configs on CPU (tests/examples) and full configs
on TPU; the compute path is the same model code the dry-run lowers.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.latency_model import FittedLatencyModel
from repro.core.request import Request, RequestState
from repro.core.token_budget import ntoken_limit
from repro.models.build import Model
from repro.serving.kv_manager import (
    KVPayload,
    PagedKVManager,
    SlotManager,
    clear_rows,
    gather_slot_kv,
    insert_rows,
    scatter_slot_kv,
)
from repro.serving.spec_decode import NGramDrafter, SpecConfig, slo_spec_len

# step ids, unique in the process: a trace's spans name a step alike
_STEP_IDS = itertools.count(1)


def _rids(reqs) -> Optional[str]:
    return ";".join(str(r.rid) for r in reqs) or None  # a span argument


def _on_rows(step, params, caches, table, rows, *args):
    """``step`` over the page table's ``rows``, selected inside the
    jitted call, so the selection adds no dispatch of its own."""
    return step(params, caches, table[rows], *args)


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 8
    max_len: int = 128
    prefill_batch: int = 4          # max sequences per prefill step
    slo_aware: bool = True          # Eq. 5 admission at the engine
    eos_token: Optional[int] = None
    # paged / chunked execution plane
    paged: Optional[bool] = None    # None = auto (paged when supported)
    page_size: int = 16
    n_pages: Optional[int] = None   # default: n_slots * ceil(max_len/ps)
    chunk_size: int = 32            # static ceiling per prefill chunk
    # fused decode blocks: max decode iterations per jitted dispatch
    # (one host sync per block instead of per token).  Every decode
    # runs as a block; the engine collapses K to 1 under queue
    # pressure so chunked prefill keeps its Eq. 5 interleave turn.
    decode_block: int = 8
    # prefix cache: page-level KV reuse across requests (paged plane,
    # pure-attention models only — SSM/conv state is slot-resident and
    # cannot ride along with shared pages)
    prefix_cache: bool = False
    prefix_cache_pages: Optional[int] = None  # cache footprint cap
    # SLO-customized speculative decoding (paged plane): an n-gram /
    # prompt-lookup drafter proposes per-lane continuations, one
    # verify dispatch scores them, and the longest greedy-matching
    # prefix is accepted (rollback = page-table truncation).  Per-lane
    # depth is picked from each request's Eq. 5 / TPOT slack, capped
    # at max_spec_len.
    spec_decode: bool = False
    max_spec_len: int = 8

    @classmethod
    def smoke(cls, **overrides) -> "EngineConfig":
        """The canonical CPU-sized engine shape examples, benchmarks,
        and CI smoke runs share (pair with smoke model configs and
        clipped workloads, e.g. ``workload.engine_smoke_workload``)."""
        kw = dict(n_slots=4, max_len=48, prefill_batch=2, page_size=8,
                  chunk_size=16)
        kw.update(overrides)
        return cls(**kw)


def home_device(params):
    """The single device holding ``params``, or None when they span
    several (a mesh-sharded replica keeps JAX's own placement)."""
    devs = {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}
    return devs.pop() if len(devs) == 1 else None


class InferenceEngine:
    def __init__(self, model: Model, params, cfg: EngineConfig,
                 profiler: Optional[FittedLatencyModel] = None,
                 fn_cache: Optional[dict] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.paged = (model.supports_chunked if cfg.paged is None
                      else cfg.paged)
        if self.paged and not model.supports_chunked:
            raise ValueError(
                "model has segments the chunked/paged plane does not "
                "support; use paged=False"
            )
        # fn_cache shares jitted step functions between engines wrapping
        # the same model/params (e.g. scaled-out EngineWorkers), so a
        # new replica doesn't pay recompilation
        cache = fn_cache if fn_cache is not None else {}
        self.slots = SlotManager(cfg.n_slots)
        self.prefix = None  # PrefixCache, attached on the paged plane
        # the replica's home device: its KV pool and page table live
        # beside its weights, so a replica on device i never computes
        # on (or silently migrates to) the default device
        self.device = home_device(params)
        if self.paged:
            self.kv = PagedKVManager(
                cfg.n_slots, cfg.max_len, cfg.page_size, cfg.n_pages,
                device=self.device,
            )
            self.caches = self._on_device(
                model.init_paged_cache, cfg.n_slots, cfg.max_len,
                cfg.page_size, self.kv.n_pages,
            )
            self.axes = model.paged_cache_axes()
            if "prefill_chunk" not in cache:
                cache["prefill_chunk"] = jax.jit(
                    partial(_on_rows, model.chunk_step))
            self._prefill_chunk = cache["prefill_chunk"]
            # a prefill chunk's program runs chunk_rows rows, one per
            # prefilling slot, where all of a model's state lives in the
            # pages; SSM/conv state is slot-resident (mamba, hybrids),
            # so their chunks run every slot's row (None)
            self.chunk_rows = (min(cfg.prefill_batch, cfg.n_slots)
                               if model.supports_prefix_cache else None)
            if cfg.prefix_cache:
                if not model.supports_prefix_cache:
                    raise ValueError(
                        "prefix caching needs pure-attention paged "
                        "caches: SSM/conv state is slot-resident, so a "
                        "shared page cannot reproduce it; disable "
                        "prefix_cache for this model"
                    )
                from repro.serving.prefix_cache import PrefixCache

                self.prefix = PrefixCache(
                    self.kv.alloc, cfg.page_size,
                    max_pages=cfg.prefix_cache_pages,
                )
                self.kv.attach_prefix_cache(self.prefix)
        else:
            if cfg.prefix_cache:
                raise ValueError(
                    "prefix caching requires the paged plane (pages are "
                    "the unit of sharing); this model/config runs the "
                    "slot fallback"
                )
            self.kv = None
            self.caches = self._on_device(
                model.init_cache, cfg.n_slots, cfg.max_len
            )
            self.axes = model.cache_axes()
        self.queue: list[Request] = []
        self.prefilling: dict[int, Request] = {}  # slot -> req
        self.active: dict[int, Request] = {}
        # P/D: prefill-complete requests whose decode runs elsewhere.
        # Pages stay resident (awaiting export), slots stay occupied,
        # but parked slots never join a decode batch.
        self.parked: dict[int, Request] = {}
        self.park_on_prefill = False  # set for prefill-role engines
        self.pos = np.zeros(cfg.n_slots, np.int32)
        self.last_token = np.zeros(cfg.n_slots, np.int32)
        # measured step times -> Appendix-A fit; an injected profiler
        # lets the cluster's Dispatcher budget on the same instance
        self.profiler = profiler if profiler is not None else (
            FittedLatencyModel()
        )
        self.finished: list[Request] = []
        self.clock = 0.0  # virtual clock advanced by measured step times

        self._prefill_fns: dict[int, Callable] = cache.setdefault(
            "prefill", {}
        )
        # the decode programs, one jitted scan per K: a fused block on
        # this plane, and a verify pass per proposal-width bucket.  The
        # stopping constants are bound in, so a fn_cache shared by
        # replicas shares a program only where they agree
        eos = -1 if cfg.eos_token is None else cfg.eos_token
        self._stop = {"eos": eos, "max_len": cfg.max_len}
        self._block_fns: dict[int, Callable] = cache.setdefault(
            ("decode_block", self.paged, eos, cfg.max_len), {}
        )
        self._spec_fns: dict[int, Callable] = cache.setdefault(
            ("spec_block", eos, cfg.max_len), {}
        )
        self._turn = "prefill"  # round-robin fairness when both planes busy
        self._seq = 0           # submit-order stamp (preemption age)
        # rid -> slot for every slotted request (prefilling / active /
        # parked) — export_kv / kv_bytes_of are O(1), not a pool scan
        self._rid_slot: dict[int, int] = {}
        # device-resident (last_token, pos): the decode-block scan's
        # final state feeds the next block directly; host-side
        # mutations (prefill completion, retire, import, preemption)
        # set the dirty flag and force a re-upload
        self._dev_state: Optional[tuple] = None
        self._host_state_dirty = True
        # telemetry for the perf trajectory (bench_decode_block)
        self.n_dispatches = 0       # jitted dispatches (= host syncs)
        self.n_decode_tokens = 0    # tokens emitted by decode steps
        self.n_prefill_tokens = 0   # prompt tokens actually prefilled
        # (cache hits skip prefill compute, so with a prefix cache this
        # undercounts l_in — exactly the FLOPs-saved figure)
        self.decode_block_hist: dict[int, int] = {}  # K -> n blocks
        # speculative decoding: drafter and acceptance telemetry
        self.drafter: Optional[NGramDrafter] = None
        self._spec_cfg: Optional[SpecConfig] = None
        self.n_spec_dispatches = 0   # propose-verify dispatches
        self.n_spec_proposed = 0     # drafted tokens sent to verify
        self.n_spec_accepted = 0     # drafted tokens accepted
        self.spec_depth_hist: dict[int, int] = {}  # pad width -> n
        # per-task acceptance stats (the SLO tiers differ by task), for
        # the per-tier speculation-depth trajectory in BENCH_spec
        self.spec_task_stats: dict[str, dict] = {}
        if cfg.page_size <= 0 or cfg.chunk_size <= 0:
            raise ValueError("page_size and chunk_size must be positive")
        if cfg.decode_block < 1:
            raise ValueError("decode_block must be >= 1")
        if cfg.spec_decode:
            if not self.paged:
                raise ValueError(
                    "spec_decode requires the paged plane: rollback is "
                    "page-table truncation"
                )
            if not model.supports_spec_decode:
                raise ValueError(
                    "spec_decode needs pure-attention paged caches: "
                    "slot-resident SSM/conv state has no per-position "
                    "record to truncate rejected tokens back to"
                )
            if cfg.max_spec_len < 1:
                raise ValueError("max_spec_len must be >= 1")
            self._spec_cfg = SpecConfig(max_spec_len=cfg.max_spec_len)
            self.drafter = NGramDrafter(
                max_ngram=self._spec_cfg.max_ngram,
                min_ngram=self._spec_cfg.min_ngram,
            )

    def _on_device(self, make, *args):
        """Build a fresh cache tree on the home device (created there,
        so the pool never passes through the default device)."""
        if self.device is None:
            return make(*args)
        with jax.default_device(self.device):
            return jax.device_put(make(*args), self.device)

    def peek_prefix(self, prompt) -> int:
        """Hit length (tokens) a prefix-cache lookup would return for
        ``prompt`` right now — read-only.  The Dispatcher's admission
        budget charges only the uncached suffix ``l_in - peek``."""
        if self.prefix is None or prompt is None:
            return 0
        return self.kv.peek_prefix(prompt)

    def kv_token_capacity(self) -> int:
        """Token capacity of this engine's KV plane (Backend protocol)."""
        if self.paged:
            return self.kv.n_pages * self.cfg.page_size
        return self.cfg.n_slots * self.cfg.max_len

    # -- intake -------------------------------------------------------------
    def validate(self, req: Request) -> None:
        """Raise if this engine could never serve ``req``.  Shared by
        ``submit`` and by the cluster's pre-run workload check, so an
        impossible request fails before the run, not mid-workload."""
        if req.prompt is None or len(req.prompt) == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if len(req.prompt) >= self.cfg.max_len:
            # the slot plane fails loudly on oversized prompts; the paged
            # plane would livelock waiting for pages that can never exist
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"leaves no room to generate within "
                f"max_len={self.cfg.max_len}"
            )
        if self.paged:
            # the request must fit the pool *alone*, so preemption can
            # always drain the pool far enough for someone to finish
            need = -(-min(len(req.prompt) + req.l_out, self.cfg.max_len)
                     // self.cfg.page_size)
            if need > self.kv.n_pages:
                raise ValueError(
                    f"request {req.rid}: needs up to {need} pages but "
                    f"the pool has {self.kv.n_pages}; raise n_pages or "
                    f"max_len/page_size"
                )

    def submit(self, req: Request) -> None:
        self.validate(req)
        if req.generated is None:
            req.generated = []
        if req.arrival is None:
            # standalone engine use: submit time is arrival time; a
            # workload generator that owns the clock sets arrival itself
            req.arrival = self.clock
        if not req.l_in:
            req.l_in = len(req.prompt)
        req.state = RequestState.ADMITTED
        req.admit_seq = self._seq
        self._seq += 1
        self.queue.append(req)

    def _prefill_fn(self, seq_len: int) -> Callable:
        if seq_len not in self._prefill_fns:
            # close over locals, not `self`: these jitted fns live in a
            # (possibly shared) fn_cache that can outlive this engine —
            # capturing `self` would pin its KV caches forever
            model, cache_len = self.model, self.cfg.max_len

            def fn(params, tokens, lens):
                return model.prefill(
                    params, tokens, lens, cache_len=cache_len
                )
            self._prefill_fns[seq_len] = jax.jit(fn)
        return self._prefill_fns[seq_len]

    # -- one engine step ------------------------------------------------------
    def step(self) -> dict:
        """Run one prefill (chunk) or decode step; returns event info
        with the step's id.  Spans: ``hfx.engine.step`` (its ``kind``,
        ``k``, ``tokens`` out; a prefill chunk's ``rows`` in its program
        and ``live_rows`` with tokens; for an expert model
        ``expert_tokens`` and ``experts_hit``) holds ``plan`` up to the
        dispatch, then ``commit`` (``_dispatch``)."""
        self._step_id = next(_STEP_IDS)
        with tracing.span("hfx.engine.step",
                          step=self._step_id) as self._span:
            self._phase = tracing.span("hfx.engine.plan")
            self._phase.__enter__()
            try:
                info = (self._step_paged() if self.paged
                        else self._step_slots())
            finally:
                self._phase.__exit__(None, None, None)
            tracing.annotate(self._span, kind=lambda: info["kind"],
                             k=lambda: info.get("k", 1),
                             tokens=lambda: info.get("tokens"))
        info["step"] = self._step_id
        return info

    def _dispatch(self, call: Callable, **args):
        """The step's jitted dispatch (``hfx.engine.launch``) and sync
        on its first output (``hfx.engine.wait``); returns the outputs
        and the measured step time.  ``args`` annotate the step."""
        tracing.annotate(self._span, **args)
        self._phase.__exit__(None, None, None)
        with tracing.span("hfx.engine.launch"):
            t0 = time.perf_counter()
            out = call()
        with tracing.span("hfx.engine.wait"):
            jax.block_until_ready(out[0])
            dt = time.perf_counter() - t0
        self.clock += dt
        self.n_dispatches += 1
        self._phase = tracing.span("hfx.engine.commit")
        self._phase.__enter__()
        return out, dt

    def _annotate_load(self, load) -> None:
        """The step's held-expert load, for a model with an expert layer
        (``load`` = its one (expert_tokens, experts_hit) output; empty
        for others): assignments computed on held experts and held
        experts with at least one, summed over layers and iterations.
        Read only while a profiler runs, after the step's sync."""
        if load:
            tracing.annotate(self._span,
                             expert_tokens=lambda: int(load[0][0]),
                             experts_hit=lambda: int(load[0][1]))

    def _step_slots(self) -> dict:
        admitted = self._admit()
        if admitted:
            return self._prefill(admitted)
        if self.active:
            return self._decode_block_step(self._decode_block_k())
        return {"kind": "idle"}

    # ==========================================================================
    # Paged / chunked plane
    # ==========================================================================
    def _step_paged(self) -> dict:
        want_prefill = bool(
            self.prefilling or (self.queue and self.slots.n_free)
        )
        if want_prefill and (not self.active or self._turn == "prefill"):
            ev = self._chunk_prefill_step()
            if ev is not None:
                self._turn = "decode"
                return ev
        if self.active:
            self._turn = "prefill"
            return self._decode_paged()
        if want_prefill:
            # decode drained while budget said "wait": force progress
            ev = self._chunk_prefill_step(force=True)
            if ev is not None:
                return ev
        return {"kind": "idle"}

    def _chunk_budget(self, force: bool) -> int:
        """Eq. 5: prompt tokens this step such that the prefill stall,
        amortized over decode iterations, keeps the tightest TPOT."""
        budget = self.cfg.chunk_size
        if force or not (self.cfg.slo_aware and self.active
                         and self.profiler.fitted):
            return budget
        cur_lens = [int(self.pos[s]) for s in self.active]
        e_d = self.profiler.decode_step_time(cur_lens)
        tightest_tpot = min(
            [r.tpot_slo for r in self.active.values()]
            + [r.tpot_slo for r in self.prefilling.values()]
            + [r.tpot_slo for r in self.queue[: self.slots.n_free]]
        )
        ttfts = ([r.ttft_slo for r in self.prefilling.values()]
                 + [r.ttft_slo for r in self.queue[: self.slots.n_free]])
        tightest_ttft = min(ttfts) if ttfts else 10.0
        n = ntoken_limit(tightest_ttft, tightest_tpot, e_d, self.profiler)
        return min(budget, n)

    def _chunk_prefill_step(self, force: bool = False) -> Optional[dict]:
        cfg = self.cfg
        # admit new requests into prefilling slots
        while (self.queue and self.slots.n_free
               and len(self.prefilling) < cfg.prefill_batch):
            r = self.queue.pop(0)
            s = self.slots.alloc(r)
            r.slot = s
            # prefix-cache hit: the slot's table starts at the shared
            # pages and prefill resumes from the hit offset — the
            # chunk-continuation path the chunked plane already runs
            r.prefill_progress = self.kv.lookup_prefix(s, r.prompt)
            r.prefix_hit_tokens = r.prefill_progress
            r.state = RequestState.PREFILLING
            self.prefilling[s] = r
            self._rid_slot[r.rid] = s
        if not self.prefilling:
            return None
        budget = self._chunk_budget(force)
        if budget <= 0:
            return None  # no decode slack: let decode run this step

        takes: dict[int, int] = {}
        rem = budget
        # admission order (dict insertion), not slot id: a later request
        # landing in a recycled low slot must not starve earlier ones
        for s, r in self.prefilling.items():
            take = min(len(r.prompt) - r.prefill_progress, cfg.chunk_size,
                       rem)
            if take > 0 and not self.kv.ensure(
                s, r.prefill_progress + take
            ):
                take = 0  # page pool dry: wait for reclamation
            takes[s] = take
            rem -= take
        if not any(takes.values()):
            if not self.active and len(self.prefilling) > 1:
                # pool dry with nothing decoding (and thus nothing to
                # retire): recompute-preempt the youngest prefill so the
                # oldest can make progress instead of livelocking
                oldest = min(self.prefilling,
                             key=lambda s: self.prefilling[s].admit_seq)
                self._preempt_youngest(exclude=oldest)
            return None

        if self.chunk_rows:
            # one row per prefilling slot in admission order; padding
            # rows (lens 0, so their writes drop) read the first one's
            slots = list(self.prefilling)
            slots += slots[:1] * (self.chunk_rows - len(slots))
            start = np.zeros(len(slots), np.int32)
        else:
            slots = list(range(cfg.n_slots))
            start = np.array(self.pos)  # decode rows: frozen at cur pos
        row = {s: slots.index(s) for s in self.prefilling}
        tokens = np.zeros((len(slots), cfg.chunk_size), np.int32)
        lens = np.zeros((len(slots),), np.int32)
        for s, r in self.prefilling.items():
            i, t = row[s], takes[s]
            tokens[i, :t] = r.prompt[
                r.prefill_progress: r.prefill_progress + t
            ]
            start[i] = r.prefill_progress
            lens[i] = t

        chunk_lens = [t for t in takes.values() if t > 0]
        (logits, self.caches, *load), dt = self._dispatch(
            lambda: self._prefill_chunk(
                self.params, self.caches, self.kv.device_table(),
                jnp.asarray(np.asarray(slots, np.int32)),
                jnp.asarray(tokens), jnp.asarray(start), jnp.asarray(lens),
            ),
            # requests whose first chunk runs now (queue wait ends here)
            first_rids=lambda: _rids(
                r for s, r in self.prefilling.items()
                if takes[s] and r.prefill_progress == r.prefix_hit_tokens),
            rows=len(slots), live_rows=len(chunk_lens),
        )
        self._annotate_load(load)
        self.profiler.observe_prefill(chunk_lens, dt)
        self.n_prefill_tokens += int(sum(chunk_lens))

        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        n_done = 0
        tok_ev: list[tuple] = []  # (rid, token, t) stream events
        for s, r in list(self.prefilling.items()):
            r.prefill_progress += takes[s]
            if takes[s] > 0 and r.prefill_progress >= len(r.prompt):
                # the slot's full-page prefix span is now immutable KV:
                # publish it so later same-prefix prompts hit
                self.kv.publish_prefix(s, r.prompt)
                tok = int(nxt[row[s]])
                if r.first_token_time is None:
                    r.first_token_time = self.clock
                r.generated.append(tok)
                r.tokens_done = len(r.generated)
                tok_ev.append((r.rid, tok, self.clock))
                self.pos[s] = len(r.prompt)
                self.last_token[s] = tok
                self._host_state_dirty = True
                del self.prefilling[s]
                done = self._is_done(r, s)
                if self.park_on_prefill and not done:
                    # P/D: decode placement is the Migrator's call —
                    # hold the KV resident until export_kv moves it
                    self.parked[s] = r
                else:
                    r.state = RequestState.DECODING
                    self.active[s] = r
                n_done += 1
        self._retire()
        return {"kind": "prefill_chunk", "tokens": int(sum(chunk_lens)),
                "n_seqs": len(chunk_lens), "n_completed": n_done,
                "time": dt, "token_events": tok_ev}

    def _preempt_youngest(self, exclude: int) -> bool:
        """Recompute preemption (the vLLM fallback for an oversubscribed
        pool): evict the youngest request — release its pages, fold its
        generated tokens into the prompt, and requeue it at the head so
        it re-prefills (and then continues generating) once pages free
        up.  Deterministic greedy decode makes the recompute exact."""
        in_flight = {**self.active, **self.prefilling}
        candidates = [s for s in in_flight if s != exclude]
        if not candidates:
            return False
        v = max(candidates, key=lambda s: in_flight[s].admit_seq)
        r = self.active.pop(v, None) or self.prefilling.pop(v)
        self._rid_slot.pop(r.rid, None)
        self._release_slot(v)
        if r.generated:
            # fold generated tokens into the prompt: the re-prefill ends
            # on the last generated token, so its next-token logits
            # continue generation exactly where decode left off.
            # r.generated keeps the full output history (l_out / eos
            # accounting stays correct).
            r.prompt = np.concatenate([
                np.asarray(r.prompt, np.int32),
                np.asarray(r.generated, np.int32),
            ])
        r.prefill_progress = 0
        r.slot = None
        r.state = RequestState.PREEMPTED
        self.queue.insert(0, r)
        return True

    def _release_slot(self, s: int) -> None:
        """Free every per-slot resource (pages, cache rows, batch row)."""
        if self.kv is not None:
            self.kv.release(s)
        self.caches = clear_rows(self.caches, self.axes, [s])
        self.slots.free(s)
        self.pos[s] = 0
        self.last_token[s] = 0
        self._host_state_dirty = True

    def evict(self, s: int) -> Optional[Request]:
        """Drop the request in slot ``s`` from the engine entirely
        (Backend ``free_kv``: its KV now lives elsewhere, e.g. after a
        migration).  Unlike preemption, the request is NOT re-queued."""
        r = (self.active.pop(s, None) or self.prefilling.pop(s, None)
             or self.parked.pop(s, None))
        if r is None:
            return None
        self._rid_slot.pop(r.rid, None)
        self._release_slot(s)
        r.slot = None
        return r

    # -- P/D hand-off (paged plane) -------------------------------------------
    def _slot_of(self, rid: int) -> Optional[int]:
        """O(1) lookup via the rid -> slot index kept in sync by the
        alloc (admission/prefill/import) and release (retire/evict/
        preempt) paths — no three-pool linear scan per export."""
        return self._rid_slot.get(rid)

    def exportable(self, rid: int) -> bool:
        """True while ``rid``'s KV is resident in a state export_kv
        accepts: parked or mid-decode, not still prefilling and not
        recompute-preempted back to the queue.  The source-side guard
        for in-flight migrations — a transfer scheduled while the
        request was exportable may land after it finished or was
        preempted, and then there is nothing left to move."""
        s = self._slot_of(rid)
        return self.paged and s is not None and s not in self.prefilling

    def export_kv(self, rid: int) -> KVPayload:
        """Materialize request ``rid``'s cache + generation state for a
        D2D hand-off.  The request must have completed prefill (parked,
        or mid-decode); its pages stay resident — the caller frees them
        via ``evict`` once the transfer has landed."""
        if not self.paged:
            raise RuntimeError(
                "export_kv requires the paged plane (slot-plane caches "
                "have no page-granular hand-off)"
            )
        s = self._slot_of(rid)
        if s is None:
            raise KeyError(f"request {rid} is not resident on this engine")
        if s in self.prefilling:
            raise RuntimeError(
                f"request {rid} has not finished prefill; its cache is "
                f"not yet a complete prefix"
            )
        n = int(self.pos[s])
        # pad the id list to the engine-constant max_pages so the
        # jitted gather compiles ONCE per leaf shape, not once per
        # prompt-length bucket (-1 entries clamp; the n_tokens slice
        # drops whatever they gather)
        ids = np.full(self.kv.max_pages, -1, np.int32)
        pages = self.kv.pages_of(s)
        ids[: len(pages)] = pages
        payload_kv = gather_slot_kv(self.caches, self.axes, s, ids, n)
        r = self.parked.get(s) or self.active.get(s)
        return KVPayload(rid=rid, n_tokens=n,
                         last_token=int(self.last_token[s]),
                         prefill_progress=r.prefill_progress,
                         kv=payload_kv)

    def import_kv(self, payload: KVPayload, req: Request) -> bool:
        """Install a migrated cache and join ``req`` to the decode
        batch mid-stream.  Allocates a slot + pages (possibly a
        different page size than the source); False if the engine
        can't place it right now (no slot / pool dry) — the caller may
        preempt and retry."""
        if not self.paged:
            raise RuntimeError("import_kv requires the paged plane")
        s = self.slots.alloc(req)
        if s is None:
            return False
        if not self.kv.ensure(s, payload.n_tokens):
            self.slots.free(s)
            return False
        # the D2D hop: the payload moves onto this replica's device
        # (a no-op when source and destination share one)
        self.caches = scatter_slot_kv(
            self.caches, self.axes, s,
            np.asarray(self.kv.pages_of(s), np.int32),
            jax.device_put(payload.kv, self.device),
        )
        if req.generated is None:
            req.generated = []
        req.slot = s
        req.prefill_progress = payload.prefill_progress
        req.state = RequestState.DECODING
        req.admit_seq = self._seq  # fresh age on this engine (preemption)
        self._seq += 1
        self.pos[s] = payload.n_tokens
        self.last_token[s] = payload.last_token
        self._host_state_dirty = True
        self.active[s] = req
        self._rid_slot[req.rid] = s
        return True

    def kv_bytes_of(self, rid: int) -> Optional[float]:
        """Exact byte size export_kv would materialize for ``rid`` —
        computed from cache shapes, nothing gathered.  The TLManager
        costs transfers on this *measured* figure rather than the
        analytic per-token estimate."""
        s = self._slot_of(rid)
        if s is None or not self.paged:
            return None
        n = int(self.pos[s])
        sizes: list[float] = []

        def acc(leaf, ax):
            if ax is None:  # paged pool: n tokens' worth of K/V
                np_, _, ps, _ = leaf.shape[-4:]
                sizes.append(leaf.size / (np_ * ps) * leaf.dtype.itemsize
                             * n)
            else:           # per-slot state: one batch row
                sizes.append((leaf.size // leaf.shape[ax])
                             * leaf.dtype.itemsize)
            return leaf

        jax.tree.map(acc, self.caches, self.axes)
        return float(sum(sizes))

    # -- fused decode blocks (both planes) -------------------------------------
    def _decode_block_k(self) -> int:
        """Pick K, the number of decode iterations to fuse this step.

        Bounded by the config ceiling, then: (a) collapsed to 1 when
        prefill work is pending — a K-block would add (K-1)*E_d to a
        waiting prompt's TTFT for zero per-token decode win, so the
        Eq. 5 chunk/decode 1:1 interleave keeps its turn; (b) capped
        by the smallest remaining output budget and max_len room over
        active requests — the valid mask would tolerate longer blocks
        (frozen lanes), but the cap trades a few extra dispatches on
        staggered completions for zero wasted lanes and a bounded wait
        before a finishing request's slot/pages are reusable by the
        next *arrival* (dispatches land between blocks); (c) rounded
        down to a power of two so the jitted block set stays bounded.
        """
        cfg = self.cfg
        k = max(1, int(cfg.decode_block))
        if k == 1 or not self.active:
            return 1
        if self.prefilling or self.queue:
            return 1
        for s, r in self.active.items():
            k = min(k, max(1, r.l_out - len(r.generated)),
                    max(1, cfg.max_len - 1 - int(self.pos[s])))
        return 1 << (k.bit_length() - 1)

    def _fit_block_k(self, k: int) -> int:
        """Shrink K (halving) until pre-reserving pages for K new
        tokens per active slot fits the free pool; at 1 the caller's
        ensure/preempt-youngest loop makes room."""
        ps = self.cfg.page_size
        while k > 1:
            need = 0
            for s in self.active:
                tgt = min(int(self.pos[s]) + k, self.cfg.max_len)
                need += max(0, -(-tgt // ps) - self.kv.n_pages_held(s))
            # unreferenced cached prefix pages count as free: ensure()
            # evicts them on demand when the reservation is drawn down
            if need <= self.kv.n_available_pages:
                return k
            k //= 2
        return 1

    def _decode_block_fn(self, k: int) -> Callable:
        if k not in self._block_fns:
            fn = (self.model.decode_block if self.paged
                  else self.model.decode_block_slots)
            self._block_fns[k] = jax.jit(partial(fn, k=k, **self._stop))
        return self._block_fns[k]

    def _spec_block_fn(self, k: int) -> Callable:
        if k not in self._spec_fns:
            self._spec_fns[k] = jax.jit(partial(
                self.model.spec_decode_block, k=k, **self._stop))
        return self._spec_fns[k]

    def _device_state(self) -> tuple:
        """(last_token, pos) as device-resident arrays.  The previous
        block's scan outputs are reused directly; any host-side
        mutation in between (prefill completion, retire, import,
        preemption) marks them dirty and forces one re-upload."""
        if self._dev_state is None or self._host_state_dirty:
            self._dev_state = jax.device_put(
                (self.last_token, self.pos), self.device
            )
            self._host_state_dirty = False
        return self._dev_state

    def _launch_block(self, fn: Callable, state, alive, rem, *extra):
        """Call a decode program (a fused block or a verify pass) the
        one way the engine does: over the caches and the page table,
        from the device ``state`` (last_token, pos), with ``alive``,
        ``rem`` and ``extra`` uploaded from the host."""
        args = (self.params, self.caches)
        if self.paged:
            args += (self.kv.device_table(),)
        return fn(*args, *state, jnp.asarray(alive), jnp.asarray(rem),
                  *map(jnp.asarray, extra))

    def warm_decode_blocks(self) -> None:
        """Compile every decode program the served path can call: the
        fused block of each power-of-two K up to ``decode_block`` and,
        with speculation, each verify bucket up to ``max_spec_len``.
        Each is called as the served path calls it, first from
        ``_device_state()``, then from its own output state, as the
        next block enters it.  Every lane is frozen and the outputs
        are dropped, so the engine's state and pool are untouched, and
        XLA compile time never lands inside a measured step."""
        cfg = self.cfg
        n = cfg.n_slots
        frozen = (np.zeros(n, bool), np.zeros(n, np.int32))
        calls = [(self._decode_block_fn(1 << i), ())
                 for i in range(cfg.decode_block.bit_length())]
        if cfg.spec_decode:
            # proposal widths round up to a power of two
            calls += [(self._spec_block_fn(1 << i),
                       (np.zeros((n, 1 << i), np.int32),
                        np.zeros(n, np.int32)))
                      for i in range((cfg.max_spec_len - 1).bit_length()
                                     + 1)]
        for fn, extra in calls:
            state = self._device_state()
            for _ in range(2):   # the output caches are dropped at once
                state = self._launch_block(fn, state, *frozen,
                                           *extra)[0][2:4]
            jax.block_until_ready(state)

    def _spec_history(self, r: Request) -> list[int]:
        """The request's true token sequence (prompt + generated).
        After a recompute preemption the prompt already contains the
        pre-preemption output, so slice to the original l_in."""
        n_in = r.l_in or len(r.prompt)
        return [int(t) for t in r.prompt[:n_in]] + [
            int(t) for t in r.generated
        ]

    def _spec_decode_step(self) -> Optional[dict]:
        """One propose-verify-accept speculative dispatch (paged plane).

        Per active lane: the SLO controller picks a depth from the
        request's TPOT slack, the n-gram drafter fills it (possibly
        with fewer tokens, possibly none — a zero-proposal lane rides
        along as a plain 1-token decode), one jitted
        ``spec_decode_block`` scores everything, and rejected lanes'
        KV is rolled back by truncating the page table to the accepted
        position.  Returns None when nothing proposes or the page pool
        can't cover the proposals even at depth 1 — the caller falls
        through to a plain decode block.
        """
        cfg = self.cfg
        ps = cfg.page_size
        cur_lens = [int(self.pos[s]) for s in self.active]
        plen: dict[int, int] = {}
        drafts: dict[int, list[int]] = {}
        want_of: dict[int, int] = {}    # controller depth (telemetry)
        for s, r in self.active.items():
            cap = min(
                self._spec_cfg.max_spec_len,
                r.l_out - len(r.generated) - 1,   # lane 0 emits one
                cfg.max_len - 1 - int(self.pos[s]),  # KV write room
            )
            want = min(
                slo_spec_len(r.tpot_slo, self.profiler, cur_lens,
                             self._spec_cfg),
                cap,
            )
            want_of[s] = want
            d = self.drafter.propose(self._spec_history(r), want)
            drafts[s] = d
            plen[s] = len(d)
        if not any(plen.values()):
            return None
        # pre-reserve pages for every lane's verify writes (positions
        # pos .. pos+plen); halve all depths until the pool fits
        while True:
            need = 0
            for s in self.active:
                tgt = min(int(self.pos[s]) + plen[s] + 1, cfg.max_len)
                need += max(0, -(-tgt // ps) - self.kv.n_pages_held(s))
            if need <= self.kv.n_available_pages:
                break
            plen = {s: p // 2 for s, p in plen.items()}
            if not any(plen.values()):
                return None
        for s in self.active:
            ok = self.kv.ensure(
                s, min(int(self.pos[s]) + plen[s] + 1, cfg.max_len)
            )
            assert ok, "spec reservation failed after availability check"

        kmax = max(plen.values())
        kpad = 1 << (kmax - 1).bit_length()  # pow2 compile bucket
        props = np.zeros((cfg.n_slots, kpad), np.int32)
        prop_lens = np.zeros(cfg.n_slots, np.int32)
        for s in self.active:
            d = drafts[s][: plen[s]]
            props[s, : len(d)] = d
            prop_lens[s] = len(d)
        alive, rem, pos0 = self._lanes()
        state = self._device_state()
        fn = self._spec_block_fn(kpad)
        (out, self.caches), dt = self._dispatch(
            lambda: self._launch_block(fn, state, alive, rem, props,
                                       prop_lens))
        self.n_spec_dispatches += 1
        self.spec_depth_hist[kpad] = self.spec_depth_hist.get(kpad, 0) + 1

        def on_lane(s: int, r: Request, emitted: list) -> None:
            accepted = max(0, len(emitted) - 1)
            self.n_spec_proposed += int(prop_lens[s])
            self.n_spec_accepted += accepted
            st = self.spec_task_stats.setdefault(
                r.task or "default",
                {"lanes": 0, "sum_want": 0, "sum_k": 0, "accepted": 0},
            )
            st["lanes"] += 1
            st["sum_want"] += want_of[s]   # controller's chosen depth
            st["sum_k"] += int(prop_lens[s])
            st["accepted"] += accepted
            if emitted:
                # rollback: rejected lanes' KV past the accepted
                # position is dead weight — give whole pages back
                self.kv.truncate(s, int(self.pos[s]) + len(emitted))

        return {**self._commit_block(out, kpad + 1, dt, pos0, on_lane),
                "spec": True}

    def _lanes(self) -> tuple:
        """The decode batch as host arrays: ``alive`` and ``rem``
        (outputs left) per slot, and each active slot's position."""
        alive = np.zeros(self.cfg.n_slots, bool)
        rem = np.zeros(self.cfg.n_slots, np.int32)
        pos0: dict[int, int] = {}
        for s, r in self.active.items():
            alive[s] = True
            rem[s] = r.l_out - len(r.generated)
            pos0[s] = int(self.pos[s])
        return alive, rem, pos0

    def _decode_block_step(self, k: int) -> dict:
        """One decode block of K iterations (either plane, K = 1
        included): a single jitted dispatch and a single host sync
        cover K tokens for every active slot, with EOS / max-len /
        l_out stopping evaluated on device (a row finishing mid-block
        freezes and its later lanes come back invalid)."""
        alive, rem, pos0 = self._lanes()
        state = self._device_state()
        fn = self._decode_block_fn(k)
        (out, self.caches), dt = self._dispatch(
            lambda: self._launch_block(fn, state, alive, rem))
        self.decode_block_hist[k] = self.decode_block_hist.get(k, 0) + 1
        return self._commit_block(out, k, dt, pos0)

    def _commit_block(self, out, k: int, dt: float, pos0: dict,
                      on_lane: Optional[Callable] = None) -> dict:
        """Commit a K-lane decode dispatch, a fused block or a
        speculative verify (whose ``on_lane(slot, req, emitted)`` runs
        before each slot's tokens land): keep its final state on the
        device, append each slot's valid lanes, feed the fit, retire."""
        toks, valid, last_f, pos_f, *load = out
        self._annotate_load(load)
        # the scan's final state IS the next block's input — resident
        self._dev_state = (last_f, pos_f)
        self._host_state_dirty = False
        tk = np.asarray(toks)   # (n_slots, K)
        vd = np.asarray(valid)  # (n_slots, K) bool
        finish_at: dict[int, float] = {}
        tok_ev: list[tuple] = []  # (rid, token, t) stream events
        n_emitted = 0
        for s, r in self.active.items():
            lanes = np.nonzero(vd[s])[0]
            emitted = [int(tk[s][i]) for i in lanes]
            if on_lane is not None:
                on_lane(s, r, emitted)
            if not emitted:
                continue
            r.generated.extend(emitted)
            r.tokens_done = len(r.generated)
            self.pos[s] += len(emitted)
            self.last_token[s] = emitted[-1]
            n_emitted += len(emitted)
            # token stamps interpolate inside the block (lane K-1 lands
            # at the engine clock), so TTFT/TPOT and the streamed stamps
            # do not depend on K — no extra host syncs: the block's one
            # sync already delivered the (n_slots, K) matrix
            stamps = self.clock - dt * (k - 1 - lanes) / k
            tok_ev += [(r.rid, tok, float(t))
                       for tok, t in zip(emitted, stamps)]
            finish_at[s] = float(stamps[-1])
        # Appendix-A attribution: K per-iteration samples of dt/K at
        # the interpolated lengths (what a block of one observes);
        # a verify's trailing all-rejected lanes are trimmed there, so
        # rejected speculation never biases the Eq. 2 fit low
        self.profiler.observe_decode_block(
            [[pos0[s] + i for s in sorted(pos0) if vd[s, i]]
             for i in range(k)], dt,
        )
        self.n_decode_tokens += n_emitted
        self._retire(finish_at)
        return {"kind": "decode", "n": len(pos0), "k": k,
                "tokens": n_emitted, "time": dt, "token_events": tok_ev}

    def _decode_paged(self) -> dict:
        cfg = self.cfg
        if (cfg.spec_decode and self.active
                and not self.prefilling and not self.queue):
            # speculate only when decode owns the step (pending prefill
            # keeps the Eq. 5 chunk/decode interleave, same as the
            # decode-block collapse-to-1 rule)
            ev = self._spec_decode_step()
            if ev is not None:
                return ev
        k = self._fit_block_k(self._decode_block_k())
        # page pre-reservation: every active slot gets room for K new
        # tokens; _fit_block_k guarantees this fits for K > 1, and at
        # K == 1 preempting the youngest request reclaims pages
        for s in list(self.active):
            if s not in self.active:  # evicted by an earlier preemption
                continue
            while not self.kv.ensure(
                s, min(int(self.pos[s]) + k, cfg.max_len)
            ):
                if not self._preempt_youngest(exclude=s):
                    raise RuntimeError(
                        "page pool exhausted with a single request in "
                        "flight — submit() sizing guard violated"
                    )
        return self._decode_block_step(k)

    # ==========================================================================
    # Slot-based plane (monolithic prefill fallback)
    # ==========================================================================
    # -- admission (Eq. 5 at the engine boundary) ------------------------------
    def _admit(self) -> list[Request]:
        free = self.slots.n_free
        if not free or not self.queue:
            return []
        take = self.queue[: min(free, self.cfg.prefill_batch)]
        if self.cfg.slo_aware and self.active:
            cur_lens = [int(self.pos[s]) for s in self.slots.active_slots()]
            e_d = self.profiler.decode_step_time(cur_lens) if (
                self.profiler.fitted
            ) else 0.0
            tightest_tpot = min(
                [r.tpot_slo for r in self.active.values()]
                + [r.tpot_slo for r in take]
            )
            tightest_ttft = min(r.ttft_slo for r in take)
            budget = ntoken_limit(
                tightest_ttft, tightest_tpot, e_d, self.profiler
            ) if self.profiler.fitted else 10 ** 9
            out, used = [], 0
            for r in take:
                if used + len(r.prompt) <= budget:
                    out.append(r)
                    used += len(r.prompt)
            take = out
        for r in take:
            self.queue.remove(r)
        return take

    def _pad_to(self, n: int) -> int:
        # pad prompt batches to a small set of shapes to bound recompiles
        p = 8
        while p < n:
            p *= 2
        return p

    def _prefill(self, reqs: Sequence[Request]) -> dict:
        b = len(reqs)
        max_l = self._pad_to(max(len(r.prompt) for r in reqs))
        tokens = np.zeros((b, max_l), np.int32)
        lens = np.zeros((b,), np.int32)
        for i, r in enumerate(reqs):
            tokens[i, : len(r.prompt)] = r.prompt
            lens[i] = len(r.prompt)
        fn = self._prefill_fn(max_l)
        (logits, cache), dt = self._dispatch(
            lambda: fn(self.params, jnp.asarray(tokens), jnp.asarray(lens)),
            first_rids=lambda: _rids(reqs))
        self.profiler.observe_prefill([len(r.prompt) for r in reqs], dt)
        self.n_prefill_tokens += int(sum(len(r.prompt) for r in reqs))

        next_tokens = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        slots = []
        tok_ev: list[tuple] = []
        for i, r in enumerate(reqs):
            s = self.slots.alloc(r)
            assert s is not None
            r.slot = s
            r.prefill_progress = len(r.prompt)
            if r.first_token_time is None:
                r.first_token_time = self.clock
            r.generated.append(int(next_tokens[i]))
            r.tokens_done = len(r.generated)
            tok_ev.append((r.rid, int(next_tokens[i]), self.clock))
            r.state = RequestState.DECODING
            self.active[s] = r
            self._rid_slot[r.rid] = s
            self.pos[s] = int(lens[i])
            self.last_token[s] = int(next_tokens[i])
            slots.append(s)
        self._host_state_dirty = True
        self.caches = insert_rows(self.caches, cache, self.axes, slots,
                                  src_rows=list(range(b)))
        self._retire()
        return {"kind": "prefill", "n": b, "time": dt,
                "token_events": tok_ev}

    # -- completion (both planes) ----------------------------------------------
    def _is_done(self, r: Request, s: int) -> bool:
        """The one completion predicate — shared by ``_retire`` and the
        chunk-prefill park decision, and mirrored in jnp on the device
        by the decode programs (``repro.models.build._stops``): output
        cap reached, EOS emitted, or no room for another token's KV
        within max_len."""
        eos = (self.cfg.eos_token is not None and r.generated
               and r.generated[-1] == self.cfg.eos_token)
        return bool(len(r.generated) >= r.l_out or eos
                    or int(self.pos[s]) + 1 >= self.cfg.max_len)

    def _retire(self, finish_at: Optional[dict] = None) -> None:
        """Move completed requests out of the decode batch.

        ``finish_at`` (slot -> time) carries interpolated stamps from a
        decode block; without it (a prefill's first token) a request
        finishes at the engine clock.
        """
        done = []
        for s, r in list(self.active.items()):
            if self._is_done(r, s):
                r.finish_time = (finish_at or {}).get(s, self.clock)
                r.state = RequestState.FINISHED
                self.finished.append(r)
                done.append(s)
                del self.active[s]
                self._rid_slot.pop(r.rid, None)
        if done:
            self.caches = clear_rows(self.caches, self.axes, done)
            for s in done:
                self.slots.free(s)
                if self.kv is not None:
                    self.kv.release(s)
                self.pos[s] = 0
                self.last_token[s] = 0
            self._host_state_dirty = True

    # -- drive to completion ------------------------------------------------------
    def run_until_done(self, max_steps: int = 10_000) -> list[Request]:
        """Step until idle; returns the requests finished during the call."""
        mark = len(self.finished)
        for _ in range(max_steps):
            if not self.queue and not self.active and not self.prefilling:
                break
            self.step()
        return self.finished[mark:]

    def fit_profiler(self) -> bool:
        return self.profiler.fit(min_samples=4)

    def release_weights(self) -> None:
        """Drop this replica's params tree (scale-in).  Every replica
        OWNS its weights (provisioned per-replica by the cluster's
        WeightManager, never aliased), so dropping the reference here
        makes the copy's device memory reclaimable.  The engine must
        not step again afterwards."""
        if self.queue or self.active or self.prefilling or self.parked:
            raise RuntimeError(
                "release_weights on an engine that still holds work; "
                "drain before scale-in"
            )
        self.params = None
