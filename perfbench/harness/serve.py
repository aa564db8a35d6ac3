"""Drive the program's served path with a cell's traffic, stamped by the client.

The window drives ``ServingSession(clock="wall")`` over
``Cluster(backend="engine")``, which runs ``EngineWorker`` ->
``InferenceEngine`` on the paged plane.  The session is single-threaded
by design, so one loop here both sends the open-loop schedule and lets
the cluster process its due events, one event at a time; every stream
event is stamped with the wall clock when it reaches this client.

Two things are put into the program from outside, both here:

- the weights, made on the device in one jitted call from the seed by
  the reference's ``init_weights`` and laid out by the family's adapter
  (the reference regenerates the same weights after the window and
  takes nothing the program made);
- no seed checkpoint on disk: ``WeightManager`` writes the whole weight
  tree under ``TMPDIR`` at every cluster build, as the source of
  scale-from-zero.  That is 7-11 GB in every run of these cells, and a
  machine that has written some tens of GB is taken out of service.  No
  cell scales out, so the write is skipped and its bytes are counted
  (``seed_checkpoint_gb``): ``setup_s`` leaves it out, and a program
  that stops writing it shows there.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

import jax

from harness.cell import Cell, seed32
from harness.traffic import Req, schedule


@dataclasses.dataclass
class Client:
    """What the client saw of one request, on its own clock."""

    req: Req
    due_t: float
    sent_t: Optional[float] = None
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    n_tok: int = 0
    finished: bool = False
    rejected: bool = False
    failed: bool = False
    request: object = None      # the program's Request (tokens, for the check)


@dataclasses.dataclass
class StepRecord:
    """One ``EngineWorker.run_step`` call, recorded in traced runs."""

    t0: float
    t1: float
    wid: int
    kind: str                   # "prefill" | "decode" | "idle"
    k: int                      # fused decode iterations (1 for prefill)
    # prefill: (start, tokens) per row; decode: (pos, emitted) per lane
    rows: list
    completed: int              # prompts that finished prefill


class Monitor:
    """Compilations seen through ``jax.monitoring`` while ``armed``."""

    def __init__(self):
        self.armed = False
        self.programs = 0       # executables obtained: compiled or loaded
        self.cache_hits = 0     # of those, read from the persistent cache
        self.traces = 0         # jaxprs traced (a new shape or function)
        self.names: list[str] = []  # functions whose programs were obtained
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **kw) -> None:
        if not self.armed:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.names.append(f"{kw.get('fun_name')} {duration:.3f}s")
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event: str, **_) -> None:
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def row(self) -> dict:
        return {"programs_obtained": self.programs,
                "compiled": self.programs - self.cache_hits,
                "cache_hits": self.cache_hits, "jaxpr_traces": self.traces,
                "functions": self.names}


def make_weights(cell: Cell, seed: int, program: bool):
    """The seed's weights on the default device, in one jitted call: in
    the program's layout, or in the reference's."""
    ref, cfg = cell.reference(), cell.config
    if program:
        adapter = cell.adapter()
        fn = jax.jit(lambda key: adapter.program_params(
            ref.init_weights(cfg, key)))
    else:
        fn = jax.jit(lambda key: ref.init_weights(cfg, key))
    return jax.block_until_ready(fn(jax.random.key(seed32(seed, "weights"))))


def build_cluster(cell: Cell, seed: int):
    """The program's cluster for this cell, holding the seed's weights,
    and the bytes of the seed checkpoint that it was kept from writing:
    0 where it wrote none, None where the program no longer writes it
    through ``repro.serving.weights.save_checkpoint`` and what it writes
    is unknown."""
    import repro.models as models
    import repro.serving.weights as weights
    from repro.serving.cluster import Cluster, ClusterConfig
    from repro.serving.engine import EngineConfig

    held = {"params": make_weights(cell, seed, program=True)}
    build = models.build_model

    def build_with_weights(cfg, **kw):
        model = build(cfg, **kw)
        # the cluster calls init once; the closure keeps nothing after
        model.init = lambda key: held.pop("params")
        return model

    skipped: dict[str, float] = {}

    def no_checkpoint(ckpt_dir, step, tree, *a, **k):
        skipped[ckpt_dir] = float(sum(x.nbytes for x in jax.tree.leaves(tree)))

    patches = {"save_checkpoint": no_checkpoint,
               "checkpoint_nbytes": lambda ckpt_dir, step: skipped[ckpt_dir]}
    kept = {n: getattr(weights, n) for n in patches if hasattr(weights, n)}
    if len(kept) == len(patches):
        for n, f in patches.items():
            setattr(weights, n, f)
    models.build_model = build_with_weights
    try:
        cluster = Cluster(ClusterConfig(
            model=cell.adapter().model_config(cell.config),
            backend="engine", n_workers=1,
            engine=EngineConfig(**cell.config["engine"]),
            seed=seed32(seed, "cluster"),
        ))
    finally:
        models.build_model = build
        for n, f in kept.items():
            setattr(weights, n, f)
    if len(kept) < len(patches):
        return cluster, None
    return cluster, float(sum(skipped.values()))


class Driver:
    """One session over one cluster, the client's stamps, and, in a
    traced run, host spans and a record of every engine step."""

    def __init__(self, cell: Cell, cluster, *, trace: bool):
        from repro.serving.session import ServingSession

        self.cell, self.cluster, self.trace = cell, cluster, trace
        self.clients: dict[int, Client] = {}
        self.steps: list[StepRecord] = []
        self.events: list = []      # (t0, t1) of Cluster.process_next, traced
        self.late: list[float] = []  # send time minus due time, in window
        self.window = (0.0, 0.0)
        self.tokens_in_window = 0
        # the three longest cluster events: (seconds, kind, start)
        self.longest = [(0.0, None, 0.0)] * 3
        self.session = ServingSession(
            cluster, clock="wall", on_event=self._on_event,
            admission=cell.traffic["admission"])
        self._rid = 0
        if trace:
            for w in cluster.workers:
                w.run_step = self._recorded(w)

    # -- client side ----------------------------------------------------------
    def _on_event(self, ev) -> None:
        t = time.perf_counter()
        c = self.clients.get(ev.rid)
        if c is None:
            return
        kind = ev.kind.value
        if kind in ("first_token", "token"):
            if c.first_t is None:
                c.first_t = t
            c.last_t = t
            c.n_tok += 1
            if self.window[0] <= t < self.window[1]:
                self.tokens_in_window += 1
        elif kind == "finished":
            c.finished = True
        elif kind == "rejected":
            c.rejected = True
        elif kind == "failed":
            c.failed = True

    def submit(self, req: Req, due_t: float) -> Client:
        c = Client(req=req, due_t=due_t)
        rid = self._rid
        self._rid += 1
        self.clients[rid] = c
        with self._span("perfbench.submit"):
            c.sent_t = time.perf_counter()
            h = self.session.submit(
                req.prompt, task=req.task, l_out=req.l_out,
                ttft_slo=req.ttft_slo, tpot_slo=req.tpot_slo, rid=rid)
        c.request = h.request
        return c

    def done(self, c: Client) -> bool:
        return c.finished or c.rejected or c.failed

    # -- the loop -------------------------------------------------------------
    def step_once(self) -> bool:
        """Process one due cluster event; False when none is due."""
        cl = self.cluster
        t = cl.next_event_time()
        if t is None or t > self.session.now:
            return False
        t0 = time.perf_counter()
        with self._span("perfbench.event"):
            kind = cl.process_next()
        t1 = time.perf_counter()
        if self.trace:
            self.events.append((t0, t1))
        if t1 - t0 > self.longest[0][0]:
            self.longest = sorted(self.longest[1:] + [(t1 - t0, kind, t0)])
        return True

    def idle(self, until: float) -> None:
        """Sleep briefly: nothing is due before ``until`` (perf clock)."""
        t = self.cluster.next_event_time()
        wait = until - time.perf_counter()
        if t is not None:
            wait = min(wait, t - self.session.now)
        if wait > 0:
            with self._span("perfbench.idle"):
                time.sleep(min(wait, 0.002))

    def run(self, reqs: list[Req], origin: float, window: tuple,
            stop: float, drain: bool, on_tick=None) -> list[Client]:
        """Send ``reqs`` at ``origin + due`` and serve until ``stop``;
        with ``drain``, stop early once every request sent in the window
        has ended.  Returns the window's clients."""
        self.window = window
        sent: list[Client] = []
        i = 0
        while True:
            now = time.perf_counter()
            while i < len(reqs) and origin + reqs[i].due <= now:
                due_t = origin + reqs[i].due
                c = self.submit(reqs[i], due_t)
                if reqs[i].in_window:
                    sent.append(c)
                    self.late.append(c.sent_t - due_t)
                i += 1
            if on_tick is not None:
                on_tick(now)
            if now >= stop:
                break
            if (drain and now >= window[1] and i == len(reqs)
                    and all(self.done(c) for c in sent)):
                break
            if not self.step_once():
                self.idle(origin + reqs[i].due if i < len(reqs) else stop)
        return sent

    def drain(self) -> None:
        """Serve until nothing is in flight (warm-up)."""
        self.session.drain()

    # -- traced runs ----------------------------------------------------------
    def _span(self, name: str):
        if self.trace:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _recorded(self, w):
        run_step = w.run_step
        eng = w.engine

        def recorded(now):
            lanes = [(int(eng.pos[s]), len(r.generated), r)
                     for s, r in eng.active.items()]
            prompts = [(r, r.prefill_progress) for r in
                       list(eng.prefilling.values()) + list(eng.queue)]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("perfbench.engine_step"):
                out = run_step(now)
            t1 = time.perf_counter()
            kind = "idle" if out is None else out.kind
            rows, k, completed = [], 1, 0
            if kind == "decode":
                k = int(out.info.get("k", 1))
                rows = [(pos, len(r.generated) - n) for pos, n, r in lanes]
            elif kind == "prefill":
                for r, start in prompts:
                    took = r.prefill_progress - start
                    if took > 0:
                        rows.append((start, took))
                        completed += int(r.prefill_progress >= len(r.prompt))
            self.steps.append(StepRecord(t0, t1, w.wid, kind, k, rows,
                                         completed))
            return out

        return recorded


def warm_up(driver: Driver, cell: Cell, seed: int) -> None:
    """Serve, before the window, what makes the program obtain every
    step program the window will run:

    - a burst of the mix's own prompts with short outputs, so that
      chunked prefill, decode and the dispatcher's latency fit have run;
    - then lone requests whose output lengths walk the fused decode
      blocks through every power-of-two K, entered both from fresh
      host state and from the previous block's device state: the
      program's own warm-up compiles each K once, on arguments that
      the served path does not pass, and a K first met in the window
      would be traced and compiled there."""
    mix, eng = cell.traffic, cell.config["engine"]
    n = eng["n_slots"] * 2
    reqs = schedule({**mix, "rate_rps": float(n), "lead_s": 0.0},
                    seed32(seed, "warmup"), 1.0, cell.config["vocab_size"],
                    eng["max_len"])
    d = eng["decode_block"]
    ks = [1 << i for i in range(d.bit_length()) if (1 << i) <= d]
    lone = sorted({2 * d + 1, 2 * d} | {k + 1 for k in ks}, reverse=True)
    origin = time.perf_counter()
    for r in reqs:
        r.l_out = min(r.l_out, 2 * d)
        driver.submit(r, origin)
    driver.drain()
    for l_out in lone:
        r = reqs[0]
        driver.submit(dataclasses.replace(r, l_out=l_out), origin)
        driver.drain()
    driver.clients.clear()


def peak_bytes() -> int:
    """Peak device bytes on the fullest chip since the process began."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))
