"""One benchmark run: set-up, the measured window, the output check.

``bench/run.py`` is the command; this module is what it drives, and what
the CPU tests drive at toy sizes. A run reads its cell from
``BENCHMARK.json`` and finds everything that belongs to the cell by name:

* ``bench/configs/<config>.json`` -- the sizes as run, the engine's
  geometry and the check's sample size and limit; beside it
  ``<config>.py``, the weights, the plain reference and the cost functions;
* ``bench/traffic/<traffic>.json`` -- the mix (see ``bench/traffic.py``);
* ``bench/metrics/<metric>.py`` -- one reader per metric;
* ``bench/peaks.json`` -- the chip's peaks by device kind.

The system under test is ``repro.serving.engine.ServingEngine``: the window
drives ``submit`` and ``step()`` on the ``pallas`` backend. ``BenchEngine``
only wraps its compute hooks with host spans and records what each
dispatched step was; it changes no decision and no number.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import devtrace, traffic  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

# After the window, requests due in it still waiting for a first token are
# given this long; one that never gets one counts as missing.
WAIT_AFTER_S = 60.0


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    model: object              # the config's module (weights, reference, costs)
    mix: Dict
    metrics: List[Dict]        # end-to-end entries of BENCHMARK.json
    per_layer: List[Dict]
    chips: int


def load_module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_" for c in tag), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = root / entry["file"]
    return Cell(
        name=workload,
        config=json.loads(cfg_path.read_text()),
        model=load_module(cfg_path.with_suffix(".py"), w["config"]),
        mix=traffic.load(w["traffic"], root / "bench" / "traffic"),
        metrics=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        chips=int(w["chips"]))


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py", "metric_" + name)


# ---------------------------------------------------------------------------
# the engine, with the benchmark's spans and records around its hooks
# ---------------------------------------------------------------------------
class BenchEngine(ServingEngine):
    """``ServingEngine`` with host spans around each dispatched step and
    around sampling, and a record of each step dispatched while
    ``recording``: which step, the rows its projections run on, its true
    tokens and start position, or each live decode slot's attended keys."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.recording = False
        self.dispatches: List[Dict] = []
        self.decode_live: List[int] = []
        self._pending: Dict = {}

    def _exec_chunk(self, w):
        self._pending = {"start": w.start, "tokens": w.true_end - w.start}
        return super()._exec_chunk(w)

    def _exec_decode(self, active_np):
        if self.recording:
            self._pending = {"keys": [r.cache_len + 1 for s, r in
                                      self.sched.running.items()
                                      if active_np[s]]}
            self.decode_live.append(int(active_np.sum()))
        return super()._exec_decode(active_np)

    def _dispatch(self, which, args):
        if self.recording:
            e = dict(self._pending, which=which, page=self.page_size,
                     rows=int(args[1].shape[0] if which == "decode"
                              else args[1].shape[1]))
            self.dispatches.append(e)
        with TraceAnnotation(f"step:{which}"):
            return super()._dispatch(which, args)

    def _sample(self, logits):
        with TraceAnnotation("sampling"):
            return super()._sample(logits)


# ---------------------------------------------------------------------------
# compilations, counted from JAX's own events
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts traces and backend compilations and sums compile seconds."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.traces = 0
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self):
        return self.traces, self.compiles

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def model_config(cell: Cell):
    """The program's registered model, run as the cell's file states: a
    field whose registered value differs from the file's takes the file's
    value, and the difference is logged."""
    from repro import configs
    mc = configs.get(cell.config["arch"])
    want = cell.model.program_fields(cell.config)
    bad = {k: v for k, v in want.items() if getattr(mc, k) != v}
    if bad:
        log(f"the program registers {cell.config['arch']} with " + ", ".join(
            f"{k} {getattr(mc, k)}" for k in bad) + f"; run with "
            f"{cell.config['name']}.json's " + ", ".join(
                f"{k} {v}" for k, v in bad.items()))
        mc = dataclasses.replace(mc, **bad)
    return mc


def jax_key(seed: int):
    """A JAX key from any whole number (the driver's seeds exceed int32)."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1)))


def make_params(cell: Cell, seed: int):
    fn = jax.jit(lambda k: cell.model.init_params(k, cell.config))
    return jax.block_until_ready(fn(jax_key(seed)))


def make_engine(cell: Cell, mc, params) -> BenchEngine:
    g = cell.config["engine"]
    return BenchEngine(
        mc, params=params, backend=g["backend"], max_slots=g["max_slots"],
        max_context=g["max_context"], page_size=g["page_size"],
        n_pages=g["n_pages"], prefill_chunk=g["prefill_chunk"],
        prefill_token_budget=g["prefill_token_budget"], temperature=0.0,
        trace=False)


def warm_lengths(engine: BenchEngine) -> List[int]:
    """Prompt lengths whose prefill reaches every compile bucket the cell
    can dispatch. A prompt is padded to whole pages, and a continuation
    chunk compiles once per prompt length in pages; a preempted request is
    re-prefilled with its tokens so far, so every length up to the context
    may come."""
    return [1] + [engine.page_size * (k - 1) + 1
                  for k in range(2, engine.max_pages_per_seq + 1)]


def warm_up(engine: BenchEngine, seed: int) -> int:
    """Serve one request of each warm length (two tokens each, so decode
    runs too) through submit and step, then drop them from the records."""
    rng = np.random.default_rng(seed)
    lens = warm_lengths(engine)
    for n in lens:
        engine.submit(rng.integers(0, engine.model_cfg.vocab, n
                                   ).astype(np.int32), max_new_tokens=2)
    while engine.sched.has_work:
        engine.step()
    jax.block_until_ready(engine.state)
    engine.requests.clear()
    return len(lens)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    """One request of the run, as the benchmark saw it."""

    item: traffic.Item
    req: object                # repro.serving.scheduler.Request
    due: float                 # absolute, engine clock
    submitted: float
    in_window: bool

    def token_times(self) -> List[float]:
        r = self.req
        if r.t_first_token is None:
            return []
        return list(r.t_first_token + np.concatenate([[0.0],
                                                      np.cumsum(r.itl_s)]))


@dataclasses.dataclass
class Run:
    """Everything the metric readers read."""

    cell: Cell
    seconds: float
    t0: float
    t1: float
    served: List[Served]
    engine: BenchEngine
    setup_s: float = 0.0
    trace: Optional[devtrace.DeviceTrace] = None
    traced: Optional[tuple] = None     # (first, end) of the traced dispatches
    peaks: Optional[Dict] = None

    @property
    def window_due(self) -> List[Served]:
        return [s for s in self.served if s.in_window]

    @property
    def traced_dispatches(self) -> List[Dict]:
        if self.traced is None:
            return []
        return self.engine.dispatches[self.traced[0]:self.traced[1]]

    def costs(self) -> List[Dict]:
        """What each step dispatched in the traced part of the window
        requires (``step_costs`` of the config's module)."""
        return [self.cell.model.step_costs(self.cell.config, e)
                for e in self.traced_dispatches]


class Hooks:
    """What the loops call at the window's edges and on every turn: the
    window's opening and closing, and, in a traced run, a profile of the
    last ``TRACE_S`` seconds of the window. Stopping the profiler stalls
    the host for seconds, so the stall comes when the window closes."""

    TRACE_S = 5.0

    def __init__(self, engine: BenchEngine, seconds: float,
                 trace_dir: Optional[str]):
        self.engine = engine
        self.seconds = seconds
        self.dir = trace_dir
        self.lo = max(0.0, seconds - self.TRACE_S)
        self.hi = seconds
        self.t0 = None
        self.on = self.done = False
        self.traced = None
        self.traced_s = 0.0
        self.start_s = self.stop_s = 0.0
        self._ann = None

    def open(self, t0: float) -> None:
        self.t0 = t0
        self.engine.dispatches.clear()
        self.engine.decode_live.clear()
        self.engine.recording = True
        self.tick(t0)

    def tick(self, now: float) -> None:
        if self.dir is None or self.done or self.t0 is None:
            return
        if not self.on and now - self.t0 >= self.lo:
            jax.block_until_ready(self.engine.state)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            t = time.monotonic()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.start_s = time.monotonic() - t
            self._ann = TraceAnnotation("bench:window")
            self._ann.__enter__()
            self._start = (len(self.engine.dispatches), time.monotonic())
            self.on = True
        elif self.on and now - self.t0 >= self.hi:
            self.stop()

    def stop(self) -> None:
        if not self.on:
            return
        self._ann.__exit__(None, None, None)
        self.traced = (self._start[0], len(self.engine.dispatches))
        self.traced_s = time.monotonic() - self._start[1]
        jax.block_until_ready(self.engine.state)
        t = time.monotonic()
        jax.profiler.stop_trace()
        self.stop_s = time.monotonic() - t
        self.on, self.done = False, True

    def close(self) -> None:
        self.engine.recording = False
        self.stop()


def _step(engine: BenchEngine) -> None:
    with TraceAnnotation("bench:scheduler"):
        engine.step()


def run_open(engine: BenchEngine, items: List[traffic.Item], seconds: float,
             clock: Callable[[], float], hooks: Hooks):
    """Open loop: submit each request when due, step while there is work,
    wait for the next arrival when there is none. After the window,
    arrivals go on until every request due in the window has its first
    token (at most ``WAIT_AFTER_S``)."""
    served: List[Served] = []
    i = 0
    t0 = clock()
    t1 = t0 + seconds
    hooks.open(t0)
    closed = False
    while True:
        now = clock()
        hooks.tick(now)
        if not closed and now >= t1:
            hooks.close()
            closed = True
        while i < len(items) and t0 + items[i].due_s <= now:
            it = items[i]
            req = engine.submit(it.prompt, max_new_tokens=it.max_new_tokens)
            served.append(Served(it, req, t0 + it.due_s, clock(),
                                 it.due_s < seconds))
            i += 1
        if closed:
            waiting = [s for s in served if s.in_window
                       and s.req.t_first_token is None
                       and s.req.state in ("queued", "running")]
            if not waiting or now - t1 > WAIT_AFTER_S:
                break
        if engine.sched.has_work:
            _step(engine)
        elif i < len(items):
            with TraceAnnotation("bench:arrivals"):
                time.sleep(max(0.0, min(t0 + items[i].due_s - clock(),
                                        0.05)))
        else:
            break
    if not closed:
        hooks.close()
    return served, t0, t1


def run_closed(engine: BenchEngine, items: List[traffic.Item],
               seconds: float, clock: Callable[[], float], hooks: Hooks,
               before_open: Callable[[], None], min_finished: int):
    """Closed loop: each client sends its next request when its last one
    is done. The first request of every client is prefilled before the
    window opens, so every slot is decoding when it does. After the window,
    the requests in flight go on (for at most ``WAIT_AFTER_S``) until
    ``min_finished`` have finished, for the output check."""
    queues: Dict[int, List[traffic.Item]] = {}
    for it in items:
        queues.setdefault(it.client, []).append(it)
    served: List[Served] = []
    current: Dict[int, Served] = {}

    def send(c: int):
        if not queues[c]:
            return
        it = queues[c].pop(0)
        req = engine.submit(it.prompt, max_new_tokens=it.max_new_tokens)
        now = clock()
        current[c] = Served(it, req, now, now, True)
        served.append(current[c])

    for c in sorted(queues):
        send(c)
    while any(s.req.state == "queued" or s.req.prefilling
              for s in current.values()):
        engine.step()
    before_open()
    t0 = clock()
    t1 = t0 + seconds
    hooks.open(t0)
    while clock() < t1:
        _step(engine)
        hooks.tick(clock())
        for c, s in list(current.items()):
            if s.req.state not in ("queued", "running"):
                send(c)
    hooks.close()
    # no new requests; the ones in flight may finish for the check
    while engine.sched.has_work and clock() - t1 < WAIT_AFTER_S and sum(
            s.req.state == "finished" for s in served) < min_finished:
        engine.step()
    return served, t0, t1


def serve(cell: Cell, engine: BenchEngine, items: List[traffic.Item],
          seconds: float, hooks: Hooks,
          before_open: Callable[[], None] = lambda: None):
    """The cell's loop over ``items``: open or closed, as its mix says."""
    if cell.mix["kind"] == "open":
        before_open()
        return run_open(engine, items, seconds, engine.now, hooks)
    return run_closed(engine, items, seconds, engine.now, hooks, before_open,
                      cell.config["check"]["sample_requests"])


# ---------------------------------------------------------------------------
# the output check
# ---------------------------------------------------------------------------
def pick_sample(served: List[Served], n: int, seed: int) -> List[Served]:
    """``n`` finished requests drawn from the seed, the one that served the
    most tokens always among them."""
    done = [s for s in served if s.req.state == "finished"
            and s.req.n_generated > 0]
    if not done:
        return []
    longest = max(done, key=lambda s: (s.req.n_generated, -s.req.rid))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed, 7])
    k = min(n - 1, len(rest))
    pick = [rest[j] for j in sorted(rng.choice(len(rest), k, replace=False))]
    return [longest] + pick


def _hidden_fn(cell: Cell, quant: bool):
    """The reference's final hidden states, one sequence at a time so that
    its attention and activations stay the size of one sequence."""
    mod, cfg = cell.model, cell.config

    def fn(params, tokens):
        h = jax.lax.map(lambda t: mod.hidden(params, t[None], cfg, quant),
                        tokens)
        return h.reshape(-1, h.shape[-1])

    return jax.jit(fn)


def _gaps_fn(cell: Cell, control: bool):
    from bench import refcommon
    mod, cfg = cell.model, cell.config

    def fn(params, h, hc, rows, targets):
        return refcommon.logit_gaps(h, rows, targets, mod.head(params, cfg),
                                    control_hidden=hc if control else None)

    return jax.jit(fn)


def check_geometry(cell: Cell):
    """(S, T, R): sequences, padded length and rows of the reference's one
    compiled shape: the check's sample size, the context, and a block
    multiple of what the sample can serve."""
    g, c = cell.config["engine"], cell.config["check"]
    s = c["sample_requests"]
    t = g["max_context"]
    r = s * cell.mix["output"]["max"]
    return s, t, -(-r // 256) * 256


def logit_gaps(cell: Cell, params, sample: List[Served], *,
               control: bool = False) -> np.ndarray:
    """The widest-gap inputs: for every token the sample served, how far
    its logit lies below the reference's best at that position (or, for
    the control, how far the fp8 reference's first choice lies below)."""
    s_max, t_max, r_max = check_geometry(cell)
    tokens = np.zeros((s_max, t_max), np.int32)
    rows = np.zeros((r_max,), np.int32)
    targets = np.zeros((r_max,), np.int32)
    n = 0
    for i, s in enumerate(sample):
        p = np.asarray(s.req.prompt, np.int32)
        g = np.asarray(s.req.generated, np.int32)
        seq = np.concatenate([p, g])
        tokens[i, :len(seq)] = seq
        k = len(g)
        rows[n:n + k] = i * t_max + len(p) - 1 + np.arange(k)
        targets[n:n + k] = g
        n += k
    tokens = jnp.asarray(tokens)
    h = _hidden_fn(cell, False)(params, tokens)
    hc = _hidden_fn(cell, True)(params, tokens) if control else h
    out = _gaps_fn(cell, control)(params, h, hc, jnp.asarray(rows),
                                  jnp.asarray(targets))
    return np.asarray(out)[:n]


def check_outputs(cell: Cell, params, served: List[Served], seed: int, *,
                  control: bool = False):
    """Returns (correct, compared): each compared number with its limit.
    With ``control``, the widest gap is the control's (the fp8 reference's
    first choice at each served position) and is judged the same way."""
    c = cell.config["check"]
    sample = pick_sample(served, c["sample_requests"], seed)
    vocab = cell.model.dims(cell.config)["V"]
    bad = sum(int(not (0 <= int(t) < vocab)) for s in sample
              for t in s.req.generated)
    short = sum(int(s.req.n_generated != s.item.max_new_tokens)
                for s in sample)
    gaps = logit_gaps(cell, params, sample, control=control) if sample \
        else np.zeros(0)
    tokens = int(gaps.size)
    widest = float(gaps.max()) if tokens else None
    compared = {
        "max_logit_gap": {"value": widest, "limit": c["max_logit_gap"]},
        "tokens_compared": {"value": tokens, "limit": c["min_tokens"]},
        "bad_token_ids": {"value": bad, "limit": 0},
        "short_requests": {"value": short, "limit": 0},
    }
    correct = (tokens >= c["min_tokens"] and widest <= c["max_logit_gap"]
               and bad == 0 and short == 0)
    return correct, compared


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------
def device_info(trace: Optional[devtrace.DeviceTrace]) -> Dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = devtrace.busy_s(trace)
        out["window_s"] = devtrace.window_s(trace)
    return out


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> Dict:
    """One run of ``cell``; returns the result line's object. ``t_start``
    is the process's start on ``time.monotonic``."""
    from repro.core import flags
    flags.set_flag("tune_mode", "off")
    counter = CompileCounter()
    phases = [("imports", time.monotonic())]
    mc = model_config(cell)
    params = make_params(cell, seed)
    phases.append(("weights", time.monotonic()))
    engine = make_engine(cell, mc, params)
    phases.append(("engine", time.monotonic()))
    n_warm = warm_up(engine, seed)
    phases.append(("warm-up", time.monotonic()))
    items = traffic.generate(cell.mix, seed, seconds, mc.vocab)
    prof_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if prof_dir:
        # the profiler's first start and stop are slow; pay them here
        jax.profiler.start_trace(prof_dir)
        jax.profiler.stop_trace()
        shutil.rmtree(prof_dir, ignore_errors=True)
        phases.append(("profiler", time.monotonic()))
    hooks = Hooks(engine, seconds, prof_dir)
    marks = {}

    def before_open():
        jax.block_until_ready(engine.state)
        marks["setup_s"] = time.monotonic() - t_start
        marks["compiles_setup"] = counter.snapshot()
        marks["compile_s"] = counter.compile_s

    served, t0, t1 = serve(cell, engine, items, seconds, hooks, before_open)
    tr_c, bc = (a - b for a, b in zip(counter.snapshot(),
                                       marks["compiles_setup"]))
    counter.close()
    tr = None
    if prof_dir:
        path = devtrace.find_xspace(prof_dir)
        tr = devtrace.DeviceTrace.from_xspace(str(path)) if path else None
        shutil.rmtree(prof_dir, ignore_errors=True)
    rec = Run(cell, seconds, t0, t1, served, engine,
              setup_s=marks["setup_s"], trace=tr, traced=hooks.traced,
              peaks=load_peaks(jax.devices()[0].device_kind))
    device = device_info(tr)
    metrics = {}
    for m in (cell.per_layer if trace else cell.metrics):
        v = metric_reader(m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    window = rec.window_due
    failed = sum(int(s.req.state == "shed" or s.req.truncated)
                 for s in window)
    lat = [s.submitted - s.due for s in served]
    ttft = [s.req.t_first_token - s.due for s in window
            if s.req.t_first_token is not None]
    log(f"device {device['kind']} x{device['count']}; set-up "
        f"{marks['setup_s']:.3f} s of which backend compiles "
        f"{marks['compile_s']:.3f} s ({marks['compiles_setup'][1]} "
        f"programs), {n_warm} warm-up prompts; phases " + ", ".join(
            f"{n} {t - t_start:.1f}" for n, t in phases))
    if hooks.traced is not None:
        log(f"traced {hooks.traced_s:.3f} s of the window, "
            f"{hooks.traced[1] - hooks.traced[0]} steps; profiler start "
            f"{hooks.start_s:.3f} s, stop {hooks.stop_s:.3f} s")
    log("TTFT s p50/p75/p90 " + "/".join(
        f"{percentile(ttft, q) or 0:.4f}" for q in (50, 75, 90))
        + "; ITL ms " + _itl_summary(rec)
        + f"; tokens in window {_tokens_in(rec)}")
    log(f"window {seconds} s: {len(window)} requests, "
        f"{len(engine.dispatches)} steps dispatched, traces {tr_c} and "
        f"compiles {bc} inside it and after it")
    log(f"arrival lateness p50 {np.median(lat) if lat else 0:.6f} s, "
        f"max {max(lat) if lat else 0:.6f} s; TTFT samples {len(ttft)} of "
        f"{len(window)}, median {np.median(ttft) if ttft else 0:.6f} s; "
        f"preemptions {int(sum(s.req.n_preempted for s in served))}; "
        f"failed {failed}")
    log(f"memory peak {device['memory_peak_bytes']} B")
    breakdown = None
    if tr is not None and tr.devices():
        d0 = tr.devices()[0]
        breakdown = {"device_ops": devtrace.top_ops(tr, d0),
                     "idle_gaps": devtrace.idle_by_activity(tr, d0)}
    # free the program's state before the reference runs
    engine.state = None
    rec.engine = None
    gc.collect()
    correct, compared = check_outputs(cell, params, served, seed)
    compared["window_compiles"] = {"value": tr_c + bc, "limit": 0}
    out = {"correct": bool(correct and tr_c + bc == 0),
           "attempted": len(window), "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = compared
    return out


def _itl_summary(run: Run) -> str:
    gaps = []
    for s in run.served:
        t = s.token_times()
        gaps += [b - a for a, b in zip(t, t[1:]) if run.t0 <= b <= run.t1]
    if not gaps:
        return "none"
    return (f"p50/p90/p95/mean {percentile(gaps, 50) * 1e3:.2f}/"
            f"{percentile(gaps, 90) * 1e3:.2f}/"
            f"{percentile(gaps, 95) * 1e3:.2f}/"
            f"{sum(gaps) / len(gaps) * 1e3:.2f} over {len(gaps)} gaps")


def _tokens_in(run: Run) -> int:
    return sum(1 for s in run.served for t in s.token_times()
               if run.t0 <= t <= run.t1)


def load_peaks(kind: str) -> Dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())["chips"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json; have {sorted(peaks)}")
    return peaks[kind]


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it. ``inf`` stands for a missing
    value."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]
