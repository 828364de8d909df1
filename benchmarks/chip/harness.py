"""Drive one cell through the program's normal serving path:
``BlockZoo`` -> ``BlockEngine.submit/step`` -> fused megastep -> paged KV
pools -> the Pallas kernel.

Set-up makes the weights, builds the zoo and engine, and serves a
warm-up stretch of the cell's own traffic from a seed stream of its own.
The window follows without draining.  Requests due in the window are the
sample; after it closes the run drains them up to the mix's cap."""
from __future__ import annotations

import gc
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import jax
import numpy as np

from benchmarks.chip import archs, model, traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


@dataclass
class Cell:
    """A configuration under a traffic mix, with the configuration's
    architecture module (by default ``archs/<model_type>.py``)."""
    name: str
    cfg: dict
    mix: dict
    chips: int = 1
    arch: Optional[ModuleType] = None

    def __post_init__(self):
        if self.arch is None:
            self.arch = archs.load(self.cfg["model_type"])


def load_cell(name: str, bench_path=ROOT / "BENCHMARK.json",
              here=HERE) -> Cell:
    bench = json.loads(Path(bench_path).read_text())
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    cfg = model.load_config(w["config"], here / "configs")
    mix = traffic.load_mix(here / "traffic" / f"{w['traffic']}.json")
    arch = archs.load(cfg["model_type"], here / "archs")
    return Cell(name, cfg, mix, w["chips"], arch)


@dataclass
class RunData:
    """Everything a metric reader may read from one run."""
    cell: Cell
    seconds: float
    setup_s: float = 0.0
    w0: float = 0.0
    w1: float = 0.0
    records: Dict[int, dict] = field(default_factory=dict)  # rid -> record
    steps: List[dict] = field(default_factory=list)  # window steps (traced)
    counters: Dict[str, Dict[str, float]] = field(default_factory=dict)
    n_due: int = 0                 # open loop: requests due in the window
    compiles: int = 0
    trace: Optional[dict] = None
    peaks: Optional[dict] = None

    @property
    def sample(self) -> List[dict]:
        return [r for r in self.records.values() if r["phase"] == "window"]

    @property
    def attempted(self) -> int:
        """Requests due in the window, submitted or not."""
        return max(self.n_due, len(self.sample))

    @property
    def done(self) -> List[dict]:
        return [r for r in self.sample if r.get("t_done") is not None]


class _CompileCounter:
    """Counts, while ``on`` is set, XLA compiles (``backend_compile``
    events), persistent-cache loads (``cache_hits``) and jaxpr traces (every
    new program, and every new shape of an eager op, starts with one)."""

    def __init__(self):
        from jax._src import monitoring
        self.on = False
        self.reset()
        monitoring.register_event_duration_secs_listener(self._event)
        monitoring.register_event_listener(self._hit)

    def reset(self) -> None:
        self.n, self.s, self.hits, self.traces, self.trace_s = 0, 0.0, 0, 0, 0.0

    def _hit(self, event: str, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _event(self, event: str, secs: float, **_):
        if not self.on:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
            self.trace_s += secs

    def summary(self) -> str:
        return (f"{self.n} compiles ({self.s:.2f} s), {self.hits} cache loads, "
                f"{self.traces} traces ({self.trace_s:.2f} s)")


def build(cell: Cell, seed: int, attn_impl: str, log=print):
    """Weights, zoo and engine for a cell."""
    t0 = time.perf_counter()
    weights = cell.arch.make_weights(cell.cfg, seed)
    jax.block_until_ready(weights)
    t1 = time.perf_counter()
    zoo = cell.arch.build_zoo(cell.cfg, weights, log)
    log(f"weights {t1 - t0:.1f} s, zoo {time.perf_counter() - t1:.1f} s")
    return weights, zoo, make_engine(cell, zoo, attn_impl)


def make_engine(cell: Cell, zoo, attn_impl: str):
    """A fresh engine over ``zoo`` with the configuration's settings."""
    from repro.serving.engine import BlockEngine, EngineConfig

    e = cell.cfg["engine"]
    engine = BlockEngine(zoo, max_len=traffic.longest_context(cell.mix),
                         config=EngineConfig(
                             max_active=e["max_active"],
                             max_block_batch=e["max_block_batch"],
                             page_size=e["page_size"],
                             num_pages=e["num_pages"],
                             attn_impl=attn_impl))
    return engine


def _sync(engine) -> None:
    jax.block_until_ready([(p.k_pages, p.v_pages)
                           for p in engine.kv.pools.values()])


class Driver:
    """The load generator and the run loop.  One process, one thread:
    submit what is due, step the engine, record what comes back."""

    SLOW_STEP_S = 1.0  # steps longer than this are logged

    def __init__(self, engine, cell: Cell, seed: int, run: RunData,
                 clock=time.perf_counter, log=print):
        self.engine, self.cell, self.seed, self.run = engine, cell, seed, run
        self.clock, self.log = clock, log
        self.vocab = cell.cfg["vocab_size"]
        self.tenants = [t["name"] for t in cell.cfg["tenants"]]
        self.pending: List[tuple] = []   # (due, phase, TrafficRequest)
        self.streams = None
        self.client_of: Dict[int, int] = {}
        self.closed_phase = "warmup"
        self.answered = [0] * cell.mix.get("clients", 0)  # per client
        self.sample_steps = False
        self.counter: Optional[_CompileCounter] = None

    # -- arrivals --------------------------------------------------------
    def schedule(self, phase: str, start: float, seconds: float) -> None:
        reqs = traffic.open_schedule(self.cell.mix, self.tenants,
                                     seed=self.seed, phase=phase,
                                     seconds=seconds)
        if phase == "window":
            self.run.n_due = len(reqs)
        for r in reqs:
            self.pending.append((start + r.due, phase, r))
        self.pending.sort(key=lambda x: x[0])

    def start_clients(self, start: float) -> None:
        mix = self.cell.mix
        self.streams = traffic.closed_streams(mix, self.tenants)
        self.pending += [(start, c, None) for c in range(mix["clients"])]

    def _submit(self, due: float, phase, req) -> None:
        from repro.serving.api import ServeRequest

        client = None
        if req is None:  # closed loop: ``phase`` is the client
            client, phase = phase, self.closed_phase
            if phase == "stop":
                return
            req = next(self.streams[client])
        toks = traffic.prompt_tokens(req, self.seed, self.vocab)
        t = self.clock()
        rid = self.engine.submit(ServeRequest(
            app=req.app, gen_len=req.out_len, prompt_tokens=toks))
        self.run.records[rid] = {
            "rid": rid, "app": req.app, "phase": phase,
            "prompt_len": req.prompt_len, "out_len": req.out_len,
            "prompt": toks, "t_due": due, "t_submit": t,
            "t_first": None, "t_done": None, "n_out": 0, "tokens": None}
        if client is not None:
            self.client_of[rid] = client

    def submit_due(self, now: float) -> None:
        i = 0
        while i < len(self.pending) and self.pending[i][0] <= now:
            i += 1
        due, self.pending = self.pending[:i], self.pending[i:]
        if due:
            with jax.profiler.TraceAnnotation("bench.submit"):
                for d, phase, req in due:
                    self._submit(d, phase, req)

    # -- the loop --------------------------------------------------------
    def _record(self, res, t: float) -> None:
        rec = self.run.records[res.rid]
        rec["t_done"] = t
        rec["t_first"] = res.info["t_first_token"]
        rec["tokens"] = np.asarray(res.tokens)
        rec["n_out"] = len(res.tokens)
        rec["queue_wait_s"] = res.info["queue_wait_s"]
        c = self.client_of.pop(res.rid, None)
        if c is not None:
            self.answered[c] += 1
            if self.closed_phase != "stop":
                self.pending.append((t, c, None))

    def _lanes(self) -> dict:
        ex, kv = self.engine.executor, self.engine.kv
        lanes = [(s.app, s.kv_len + ex.buffered(s.rid),
                  sum(b.has_kv for b, _ in s.steps))
                 for s in self.engine.active]
        return {"lanes": lanes,
                "reserved_pages": sum(p.used_pages for p in kv.pools.values()),
                "page_size": self.engine.config.page_size}

    def loop(self, until) -> None:
        """Run until ``until(now)`` is true."""
        engine = self.engine
        while True:
            now = self.clock()
            if until(now):
                return
            self.submit_due(now)
            if engine.active or engine.scheduler.waiting:
                before = self._lanes() if self.sample_steps else None
                t0 = self.clock()
                with jax.profiler.TraceAnnotation("bench.engine_step"):
                    out = engine.step()
                t1 = self.clock()
                if t1 - t0 > self.SLOW_STEP_S and self.run.w0:
                    self.log(f"slow step: {t1 - t0:.2f} s at window "
                             f"+{t0 - self.run.w0:.2f} s, "
                             f"{len(engine.active)} active; in the window "
                             f"so far {self.counter.summary()}")
                for res in out or ():
                    self._record(res, t1)
                if before is not None:
                    before.update(t0=t0, t1=t1)
                    self.run.steps.append(before)
            else:
                nxt = self.pending[0][0] if self.pending else now + 0.01
                with jax.profiler.TraceAnnotation("bench.idle_wait"):
                    time.sleep(min(max(0.0, nxt - self.clock()), 0.05))


def _counters(engine) -> Dict[str, float]:
    return {k: float(v) for k, v in engine.stats.items()}


# jax.monitoring listeners cannot be removed, so one counter serves the
# whole process and each run resets it
_COUNTER: Optional[_CompileCounter] = None


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool = False,
             attn_impl: str = "pallas", trace_dir: Optional[Path] = None,
             t_start: Optional[float] = None,
             log=print) -> tuple:
    """Set up, warm up, measure ``seconds``, drain.  Returns (run data,
    weights, engine); the caller frees the engine before the reference.
    Set-up is timed from ``t_start`` (perf_counter) if given."""
    global _COUNTER
    mix = cell.mix
    run = RunData(cell=cell, seconds=seconds)
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    counter = _COUNTER
    counter.reset()
    counter.on = True
    t_start = time.perf_counter() if t_start is None else t_start
    weights, zoo, engine = build(cell, seed, attn_impl, log)
    log(f"build: {counter.summary()}")
    log(f"built {len(zoo.blocks)} blocks, {zoo.zoo_bytes() / 1e9:.3f} GB, "
        f"apps {list(zoo.chains)} in {time.perf_counter() - t_start:.1f} s")
    drv = Driver(engine, cell, seed, run, log=log)
    drv.counter = counter
    t0 = time.perf_counter()
    closed = mix["loop"] == "closed"
    if closed:
        drv.start_clients(t0)
        n = int(mix["warmup_rounds"])
        drv.loop(lambda now: min(drv.answered) >= n)
    else:
        warm = float(mix["warmup_s"])
        drv.schedule("warmup", t0, warm)
        drv.loop(lambda now: now >= t0 + warm)
    # the window follows at once; set-up ends where it starts
    run.w0 = time.perf_counter()
    run.w1 = run.w0 + seconds
    run.setup_s = run.w0 - t_start
    if closed:
        drv.closed_phase = "window"
    else:
        drv.schedule("window", run.w0, seconds)
        drv.schedule("tail", run.w1, float(mix["drain_cap_s"]))
    run.counters["start"] = _counters(engine)
    drv.sample_steps = trace
    log(f"set-up: {counter.summary()}")
    counter.reset()
    if trace:
        _traced_window(drv, run, engine, trace_dir, float(mix["traced_s"]))
    else:
        drv.loop(lambda now: now >= run.w1)
    counter.on = False
    run.compiles = counter.n + counter.hits
    window = counter.summary()
    run.counters["end"] = _counters(engine)
    drv.sample_steps = False
    if closed:
        drv.closed_phase = "stop"
    cap = run.w1 + float(mix["drain_cap_s"])
    drv.loop(lambda now: now >= cap or (
        len(run.done) == run.attempted))
    _sync(engine)
    log(f"window: {run.attempted} due, {len(run.done)} done; in the "
        f"window {window}")
    return run, weights, engine


def _traced_window(drv: Driver, run: RunData, engine, trace_dir: Path,
                   traced_s: float) -> None:
    """Run the window, profiling a ``traced_s`` stretch in its middle."""
    from benchmarks.chip import xplane

    a = run.w0 + max(0.0, (run.seconds - traced_s) / 2)
    drv.loop(lambda now: now >= a)
    _sync(engine)
    trace_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir))
    ta = time.perf_counter()
    drv.loop(lambda now: now >= ta + traced_s)
    _sync(engine)
    tb = time.perf_counter()
    jax.profiler.stop_trace()
    drv.loop(lambda now: now >= run.w1)
    run.trace = xplane.reduce_dir(trace_dir)
    run.trace["host_window"] = (ta, tb)


def free(engine) -> None:
    """Drop the engine's device state (pools, decode states, programs)."""
    engine.kv.pools.clear()
    engine.executor.decode_states.clear()
    engine.active.clear()
    gc.collect()
