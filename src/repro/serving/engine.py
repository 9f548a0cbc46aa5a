"""Request-level serving engine: continuous batching over paged KV caches.

``ServingEngine`` binds a model, its parameters, one jitted paged-prefill
and one jitted paged-decode computation, the page allocator, and the
scheduler into the loop a serving binary runs:

    engine = ServingEngine(configs.get_smoke("gemma3-1b"), max_slots=4)
    engine.submit(prompt, max_new_tokens=32)
    report = engine.run()            # drains the queue

Static shapes throughout (XLA/jit discipline): the decode batch is always
``max_slots`` wide -- empty or finished slots decode padding into the trash
page -- and prefill pads prompts up to a page-size multiple so distinct
prompt lengths share compile-cache buckets. The *contents* are fully
dynamic: requests enter and leave slots every iteration, which is exactly
the contention the static batch loop (``policy="static"``: admission
barrier, no slot recycling) cannot express.

Chunked prefill (``prefill_chunk``) extends the discipline to long
prompts: fixed-size chunks are their own compile buckets (the traced
``start`` offset keeps one bucket per chunk *length*), mid-prefill slots
ride the decode batch as padding with frozen lengths AND frozen recurrent
state, and only the final chunk samples a token.

Per-request numerics are batch-invariant: projections, norms, and the
paged attention path are row-independent, so a request decoded alongside
arbitrary co-tenants produces bit-identical tokens to the same request
decoded alone through the dense-cache oracle (``transformer.decode_step``;
tests/test_serving_families.py gates on this).

Control-plane / compute split: every *decision* the engine makes --
admission, chunk ordering, preemption, recovery-ladder control flow,
token-commit accounting, spill/restore protocol -- lives on
:class:`EngineControlPlane`, which never touches a device tensor. The
device work (jitted step dispatch, sampling, table sync, DMA copies) is
behind a handful of compute hooks ``ServingEngine`` implements. A null
executor (``repro.analysis.mc.harness.NullEngine``) implements the same
hooks with fabricated deterministic token commits, which is what lets the
model checker exhaust scheduler x allocator x recovery interleavings
without a model; the same seam is where speculative-decoding verify steps
and a sequence-sharded multi-host arena plug in (ROADMAP).
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flags
from repro.core.config import GemminiConfig
from repro.core.context import ExecutionContext
from repro.core.generator import default_engine_backend
from repro.models import transformer as tf
from repro.obs import trace as otrace
from repro.obs.metrics import MetricsRegistry
from repro.runtime import faults as rfaults
from repro.runtime.ft import StepWatchdog
from repro.serving.paged_cache import PagedKVAllocator, arena_pages
from repro.serving.scheduler import ContinuousScheduler, Request, summarize


# Jitted step functions shared across ServingEngine instances: jax.jit
# caches per function object, so per-engine lambdas would recompile every
# prefill/decode bucket on every engine construction (e.g. a policy A/B
# builds several engines over one model).
# Keyed by everything the closures bake in; both configs are frozen
# dataclasses, so the key is value-hashed, not identity-hashed.
_JIT_CACHE: Dict = {}


def _jitted_steps(engine: ExecutionContext, model_cfg, page_size: int,
                  donate: bool = True):
    """The five jitted model steps, keyed by name.

    ``donate=False`` keeps the state argument alive across a call: the
    NaN/Inf-guard path re-runs the *same pre-call state* on the XLA twin
    after the primary backend produced non-finite logits, which is only
    sound if the primary call did not consume the buffer. Guarded engines
    therefore trade one extra in-flight state copy for an exact degraded
    mode; unguarded engines (the default) keep the donating fast path."""
    key = (engine, model_cfg, page_size, donate)
    if key not in _JIT_CACHE:
        dn = (2,) if donate else ()
        prefill = jax.jit(
            lambda p, tok, st, slot, pages: tf.paged_prefill(
                engine, p, model_cfg, tok, st, slot, pages,
                page_size=page_size),
            donate_argnums=dn)
        # Logits-free twins for intermediate chunks: nothing samples until
        # the last chunk, so they skip the unembed vocab GEMM entirely.
        prefill_nl = jax.jit(
            lambda p, tok, st, slot, pages: tf.paged_prefill(
                engine, p, model_cfg, tok, st, slot, pages,
                page_size=page_size, with_logits=False),
            donate_argnums=dn)
        # Continuation chunks additionally carry the STATIC kv_pages bound
        # (admission-time prompt footprint in pages): one compile bucket
        # per (chunk length, kv_pages) pair, and the gather attention only
        # contracts the table prefix that can ever hold live keys.
        chunk = jax.jit(
            lambda p, tok, st, slot, pages, start, kv_pages:
            tf.paged_prefill_chunk(
                engine, p, model_cfg, tok, st, slot, pages, start,
                page_size=page_size, kv_pages=kv_pages),
            donate_argnums=dn, static_argnums=(6,))
        chunk_nl = jax.jit(
            lambda p, tok, st, slot, pages, start, kv_pages:
            tf.paged_prefill_chunk(
                engine, p, model_cfg, tok, st, slot, pages, start,
                page_size=page_size, with_logits=False, kv_pages=kv_pages),
            donate_argnums=dn, static_argnums=(6,))
        decode = jax.jit(
            lambda p, tok, st, act: tf.paged_decode_step(
                engine, p, model_cfg, tok, st, act, page_size=page_size),
            donate_argnums=dn)
        _JIT_CACHE[key] = {"prefill": prefill, "prefill_nl": prefill_nl,
                           "chunk": chunk, "chunk_nl": chunk_nl,
                           "decode": decode}
    return _JIT_CACHE[key]


def _env_check_default() -> bool:
    """``$GEMMINI_CHECK`` truthiness: the step-boundary allocator-invariant
    knob's environment default (off unless set to 1/true/on/yes)."""
    return os.environ.get("GEMMINI_CHECK", "").strip().lower() in (
        "1", "true", "on", "yes")


class EngineControlPlane:
    """The device-free half of the serving engine.

    Everything that *decides* lives here: submission, the per-iteration
    step structure (shed -> prefill chunks -> decode capacity -> decode),
    token-commit accounting (``_record_token`` and the finish/EOS logic),
    the recovery ladder's control flow (``_run_guarded``: transient retry
    -> NaN guard -> fallback -> quarantine), and the host-offload
    spill/restore protocol. None of it touches a device tensor; the
    compute work is behind the hooks below, which a subclass implements:

    * :meth:`_dispatch` / :meth:`_dispatch_fallback` -- run one model step
      (primary / degraded-mode twin), returning ``(logits, state)``.
    * :meth:`_exec_chunk` -- execute one prefill chunk's compute; returns
      the sampled token for the last chunk, else None.
    * :meth:`_exec_decode` -- execute one decode step's compute; returns
      per-slot sampled tokens.
    * :meth:`_capture_spill` / :meth:`_apply_restore` -- the device<->host
      copies behind the offload accounting.
    * :meth:`_sync_tables` -- push allocator block tables to the device
      (no-op by default: a tensor-free executor has no tables to sync).
    * :meth:`_bucket_key` -- the compile-bucket key of a dispatch, for the
      trace-time jit audit (default: one bucket).

    ``ServingEngine`` implements the hooks against the jitted model steps;
    ``repro.analysis.mc.harness.NullEngine`` implements them with
    fabricated deterministic token commits so the model checker can step
    the REAL scheduling/recovery logic through exhaustive interleavings.

    Subclasses finish construction by setting the geometry and component
    attributes: ``max_context``, ``page_size``, ``max_pages_per_seq``,
    ``prefill_pad``, ``alloc``, ``sched``, ``prefill_chunk``,
    ``_next_token``.
    """

    def __init__(self, model_cfg, *, max_slots: int,
                 policy: str = "continuous",
                 faults=None,
                 nan_guard: Optional[bool] = None,
                 max_step_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 assert_invariants: Optional[bool] = None,
                 watchdog: Optional[StepWatchdog] = None,
                 trace=None,
                 clock=None):
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {policy!r}")
        self.model_cfg = model_cfg
        self.policy = policy
        self.max_slots = max_slots
        # -- observability (docs/observability.md) -------------------------
        # One monotonic clock for every duration in the engine (wall
        # clocks step under NTP); the tracer and scheduler share it so
        # span timestamps and request timings live in one domain.
        self.clock = clock or time.monotonic
        self.tracer = otrace.as_tracer(trace, clock=self.clock)
        self.metrics = MetricsRegistry()
        # The host loop's phase spans (engine.step and its children),
        # always on as registry observations; the device-backed engine
        # adds profiler annotations, and the ring gets them only when
        # tracing is on.
        self.spans = otrace.Spans(self.metrics, self.clock, self.tracer)
        # Bail-out cap for run(): overridable so tests can force the hang
        # diagnostics without 100k iterations.
        self.max_run_iters = 100_000
        # -- robustness envelope (docs/serving.md#robustness) --------------
        # faults: None consults $GEMMINI_FAULTS (usually: off); a spec
        # string / FaultPlan / FaultInjector turns deterministic fault
        # injection on for THIS engine only. nan_guard defaults to
        # "on iff faults are on": the guard host-checks every step's
        # logits, and the fault-free fast path must stay byte-identical
        # to PR 5 (donating jits, no per-step isfinite sync).
        self.faults = rfaults.as_injector(faults)
        if self.faults is not None and self.tracer is not None:
            # Fault firings land on this engine's trace (cat="fault"),
            # not just on a globally installed tracer.
            self.faults.tracer = self.tracer
        self.nan_guard = (self.faults is not None) if nan_guard is None \
            else nan_guard
        self.max_step_retries = max_step_retries
        self.retry_backoff_s = retry_backoff_s
        # Debug oracle: run PagedKVAllocator.check() at every step
        # boundary. Off by default (it is O(pages) of pure-Python asserts
        # on the hot loop); None consults $GEMMINI_CHECK so the chaos
        # suite -- and any bug hunt -- can flip it on without code edits.
        self.assert_invariants = _env_check_default() \
            if assert_invariants is None else bool(assert_invariants)
        # per-step-name set of dispatched compile-bucket keys, consumed by
        # the trace-time auditor (repro.analysis.lint.jit_audit): every
        # distinct key is one XLA compilation, and the static census from
        # the page/chunk geometry caps how many may ever exist.
        self.observed_buckets: Dict[str, set] = {}
        self.quarantined: List[str] = []
        self.watchdog = watchdog or StepWatchdog()
        # The tuned schedule the decode path launches, for quarantine on a
        # guard trip (subclasses resolve it when tuning is on).
        self._paged_sched_key: Optional[str] = None
        self._rid = 0
        self.requests: List[Request] = []

    # -- compute hooks (subclass responsibility) ---------------------------
    def _dispatch(self, which: str, args: tuple):
        """Run one primary model step; returns ``(logits, state)``."""
        raise NotImplementedError

    def _dispatch_fallback(self, which: str, args: tuple):
        """Run one degraded-mode (bit-exact twin) model step."""
        raise NotImplementedError

    def _exec_chunk(self, w):
        """Execute one prefill chunk's compute against the device state.
        Must return the sampled token when ``w.last`` (the chunk whose
        final row is the prompt's last true position), else None."""
        raise NotImplementedError

    def _exec_decode(self, active_np: np.ndarray) -> np.ndarray:
        """Execute one decode step's compute; returns sampled tokens
        indexed by slot (inactive slots' entries are ignored)."""
        raise NotImplementedError

    def _capture_spill(self, req: Request, page_ids: List[int]) -> Dict:
        """Device->host copy of a victim's committed pages (plus any
        per-slot recurrent state): the opaque host-pool payload."""
        raise NotImplementedError

    def _apply_restore(self, req: Request, slot: int, spill) -> None:
        """Host->device copy of a spill payload into a fresh slot."""
        raise NotImplementedError

    def _sync_tables(self, slots) -> None:
        """Push the allocator's block tables for ``slots`` to the device
        state. Default: no-op (tensor-free executors keep no tables)."""

    def _bucket_key(self, which: str, args: tuple):
        """The compile-bucket a dispatch lands in (jit-audit census)."""
        return ()

    # -- observability -----------------------------------------------------
    def now(self) -> float:
        """The engine clock (monotonic by default). ``submit(deadline=)``
        timestamps must come from this domain: ``engine.now() + rel_s``,
        never ``time.time() + rel_s``."""
        return self.clock()

    @property
    def counters(self) -> Dict[str, int]:
        """Read-only robustness-counter view over the metrics registry
        (the pre-obs ``engine.counters`` dict shape, kept for callers;
        new code should read ``engine.metrics`` directly)."""
        return {"retries": int(self.metrics.value("retries")),
                "fallbacks": int(self.metrics.value("fallbacks"))}

    def _step_gauges(self) -> None:
        """Per-iteration occupancy gauges (registry + tracer counter
        track): arena pages, live/prefilling slots, queue depth."""
        t = self.clock()
        used = self.alloc.used_pages
        live = sum(1 for r in self.sched.running.values()
                   if not r.prefilling)
        depth = len(self.sched.queue)
        self.metrics.gauge("arena_used_pages").set(used, t)
        self.metrics.gauge("arena_utilization").set(
            self.alloc.utilization, t)
        self.metrics.gauge("live_slots").set(live, t)
        self.metrics.gauge("running_slots").set(
            len(self.sched.running), t)
        self.metrics.gauge("queue_depth").set(depth, t)
        if self.tracer is not None:
            self.tracer.counter("arena_pages", used=used,
                                free=self.alloc.free_pages)
            self.tracer.counter("slots", live=live,
                                running=len(self.sched.running))
            self.tracer.counter("queue_depth", depth=depth)

    # -- submission --------------------------------------------------------
    def _bucket(self, n: int) -> int:
        return -(-max(1, n) // self.prefill_pad) * self.prefill_pad

    def submit(self, prompt, max_new_tokens: int, *,
               eos_id: int = -1, priority: int = 0,
               deadline: Optional[float] = None) -> Request:
        """``priority``/``deadline`` feed the scheduler's admission order
        (no-ops under the default FIFO policy); ``deadline`` is an
        absolute timestamp in the ENGINE clock's domain
        (``engine.now() + rel_s`` -- monotonic by default, not
        ``time.time()``)."""
        prompt = np.asarray(prompt, np.int32)
        need = self._bucket(len(prompt)) + self.model_cfg.n_meta_tokens
        cap = min(self.max_pages_per_seq,
                  self.alloc.n_pages) * self.page_size
        if need > cap:
            raise ValueError(f"prompt of {len(prompt)} tokens can never be "
                             f"admitted (cache capacity {cap} tokens, "
                             f"max_context={self.max_context})")
        req = Request(rid=self._rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      priority=priority, deadline=deadline)
        self._rid += 1
        self.requests.append(req)
        self.sched.submit(req)
        return req

    # -- token commit ------------------------------------------------------
    def _record_token(self, req: Request, tok: np.ndarray,
                      now: float) -> None:
        req.generated.append(tok if tok.ndim else int(tok))
        if req.t_first_token is None:
            req.t_first_token = now
        else:
            req.itl_s.append(now - req.t_last_token)
        req.t_last_token = now
        self._next_token[req.slot] = tok
        if self.tracer is not None:
            self.tracer.instant("token", cat="request",
                                tid=otrace.req_tid(req.rid),
                                n=req.n_generated)
        done = req.n_generated >= req.max_new_tokens
        if self.model_cfg.n_codebooks == 1 and int(tok) == req.eos_id:
            done = True
        if done:
            if self.tracer is not None and req.t_first_token is not None:
                # The request's decode phase as one span: first token
                # (end of prefill) to last.
                self.tracer.complete("decode", req.t_first_token, now,
                                     cat="request",
                                     tid=otrace.req_tid(req.rid),
                                     tokens=req.n_generated)
            self.sched.finish(req)

    # -- KV lifecycle: host offload (scheduler-wired hooks) ----------------
    def _spill(self, req: Request, page_ids: List[int],
               committed: int) -> bool:
        """Host-pool spill of a preemption victim's committed pages. Runs
        BEFORE ``free_slot`` re-issues the pages; the :meth:`_capture_spill`
        hook forces the device->host copy to complete while contents are
        still exclusively owned. Returns False (degrade to recompute) on
        an injected ``offload_io@spill`` fault or when the pool rejects
        the entry."""
        inj = self.faults
        if inj is not None and inj.offload_fails("spill"):
            return False
        if not page_ids:
            return False
        payload = self._capture_spill(req, page_ids)
        ok = self.alloc.host_put(req.rid, len(page_ids), committed, payload)
        if ok:
            self.metrics.counter("offload_spills").inc()
        return ok

    def _restore(self, req: Request, slot: int, committed: int) -> bool:
        """Host-pool restore into a freshly allocated slot (the scheduler
        allocated BEFORE calling, so the target pages exist and are
        exclusive; :meth:`_apply_restore` performs the copies). Returns
        False to degrade the admission to recompute: injected
        ``offload_io@restore`` fault, or a stale/missing spill entry."""
        inj = self.faults
        if inj is not None and inj.offload_fails("restore"):
            self.alloc.host_drop(req.rid)
            return False
        sp = self.alloc.host_take(req.rid)
        if sp is None or sp.tokens != committed:
            return False
        self._apply_restore(req, slot, sp)
        self.metrics.counter("offload_restores").inc()
        return True

    # -- robustness envelope ----------------------------------------------
    def _quarantine(self, site: str) -> None:
        """Bar the tuned schedule behind a guard trip from future
        resolution (PlanCache.quarantine). Only the decode path maps 1:1
        to one tuned schedule (the paged-attention key the page size was
        resolved under); prefill trips still fall back + count, but have
        no single schedule to blame."""
        key = self._paged_sched_key if site == "decode" else None
        if key is None or key in self.quarantined:
            return
        from repro import tune
        tune.get_cache().quarantine(key)
        self.quarantined.append(key)

    def _run_guarded(self, site: str, which: str, args: tuple):
        """One model step under the robustness envelope.

        Order of events: (1) injected transient failures raise *before*
        the call and retry with bounded exponential backoff -- state is
        untouched, so a retry is a plain re-dispatch; (2) the injector may
        poison the returned logits (host-level: compiled functions stay
        byte-identical to the fault-free run); (3) with ``nan_guard`` on,
        non-finite logits trigger one retry of the SAME step on the XLA
        twin from the SAME pre-call state (non-donating jits keep it
        alive), the tuned schedule is quarantined, and the fallback is
        counted in telemetry. A twin that still produces non-finite
        logits means the model itself diverged -- that raises, because
        sampling from NaN logits would silently emit garbage tokens.
        """
        seen = self.observed_buckets.setdefault(which, set())
        bucket = self._bucket_key(which, args)
        new_bucket = bucket not in seen
        seen.add(bucket)
        inj = self.faults
        for attempt in range(self.max_step_retries + 1):
            try:
                if inj is not None:
                    inj.check_transient(site)
                with self.spans("engine.dispatch", which=which,
                                new_bucket=new_bucket):
                    logits, state = self._dispatch(which, args)
                break
            except rfaults.TransientOpError:
                self.metrics.counter("retries", site=site).inc()
                if self.tracer is not None:
                    self.tracer.instant("retry", cat="engine", site=site,
                                        which=which, attempt=attempt + 1)
                if attempt == self.max_step_retries:
                    raise
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** attempt))
        if inj is not None and logits is not None:
            logits = inj.poison(site, logits)
        if self.nan_guard and logits is not None:
            with self.spans("engine.wait"):
                finite = bool(np.isfinite(np.asarray(logits)).all())
            if not finite:
                self.metrics.counter("fallbacks", site=site).inc()
                if self.tracer is not None:
                    self.tracer.instant("fallback", cat="engine", site=site,
                                        which=which)
                self._quarantine(site)
                logits, state = self._dispatch_fallback(which, args)
                if not bool(np.isfinite(np.asarray(logits)).all()):
                    raise FloatingPointError(
                        f"non-finite logits at {site!r} survived the XLA "
                        f"fallback: model divergence, not a kernel fault")
        return logits, state

    # -- execution (control skeletons over the compute hooks) --------------
    def _do_prefill_chunk(self, w) -> None:
        """Execute one scheduler-issued prefill chunk: run the compute
        hook, then commit the accounting (cache_len, prefix publication)
        and -- for the last chunk -- record the sampled token."""
        req, slot = w.req, w.slot
        if req.state != "running" or req.slot != slot:
            # The scheduler finished or preempted this request AFTER
            # emitting the chunk (sole-runner truncation later in the same
            # pass): its pages are freed -- executing the chunk would
            # scatter into a zero table row over pages the allocator may
            # already have re-issued.
            return
        t0 = self.clock()
        tok = self._exec_chunk(w)
        with self.spans("engine.commit"):
            req.cache_len = w.true_end
            req.n_chunks += 1
            self.sched.note_committed(req)
            if self.tracer is not None:
                if w.first and w.last:
                    self.tracer.complete("prefill", t0, cat="request",
                                         tid=otrace.req_tid(req.rid),
                                         slot=slot, tokens=w.true_end)
                else:
                    self.tracer.complete(
                        f"prefill_chunk[{req.n_chunks - 1}]", t0,
                        cat="request", tid=otrace.req_tid(req.rid),
                        slot=slot, start=w.start, end=w.true_end,
                        last=w.last)
            if w.last:
                self._record_token(req, tok, self.clock())

    def _do_decode(self) -> None:
        active_np = np.zeros((self.max_slots,), bool)
        for slot, req in self.sched.running.items():
            # Mid-prefill slots hold pages but must not decode: inactive
            # slots write the trash page and keep frozen lengths, so a
            # partially-prefilled cache can never be touched.
            active_np[slot] = not req.prefilling
        last = self._exec_decode(active_np)
        with self.spans("engine.commit"):
            now = self.clock()
            for slot, req in list(self.sched.running.items()):
                if req.prefilling:
                    continue
                req.cache_len += 1
                self._record_token(req, last[slot], now)

    # The two step phases, exposed individually so the model checker can
    # interleave them as atomic actions; step() composes exactly these, so
    # the checked control flow and the served control flow are one code
    # path (no re-model to drift).
    def control_prefill(self, admit_new: bool = True) -> int:
        """Admission-boundary phase: shed expired deadlines, execute the
        scheduler's prefill chunk queue, drain unservable rejections.
        Returns the number of chunks executed."""
        with self.spans("engine.plan"):
            self.sched.shed_expired()
            ws = self.sched.prefill_schedule(admit_new=admit_new)
        for w in ws:
            self._do_prefill_chunk(w)
        if self.sched.rejected:
            with self.spans("engine.plan"):
                for req in self.sched.rejected:
                    # Regrew past the arena while preempted: finish
                    # truncated.
                    self.sched.finish(req, truncated=True)
                self.sched.rejected = []
        return len(ws)

    def control_decode(self) -> None:
        """Decode-boundary phase: ensure every running slot can take one
        more token (preempting by eviction under pressure), shed expired
        deadlines, decode one token per fully-prefilled running slot."""
        with self.spans("engine.plan"):
            new_pages, _evicted, _trunc = \
                self.sched.ensure_decode_capacity()
        if new_pages:
            with self.spans("engine.prep"):
                self._sync_tables({slot for slot, _ in new_pages})
        with self.spans("engine.plan"):
            self.sched.shed_expired()
        if any(not r.prefilling for r in self.sched.running.values()):
            self._do_decode()

    def step(self) -> None:
        """One scheduler iteration: shed expired deadlines (admission
        boundary), prefill (whole prompts, or chunks interleaved at
        ``prefill_chunk`` granularity), ensure decode capacity (preempting
        by eviction under pressure), shed expired deadlines again (decode
        boundary), decode one token for every fully-prefilled running
        slot. With faults on, the injector runs first: straggler sleeps
        and one iteration's worth of arena pressure (pages withheld for
        the whole step, so the scheduler's can_admit-then-alloc protocol
        stays consistent, then released). With ``assert_invariants`` on
        (``GEMMINI_CHECK``), the allocator's ownership oracle runs at the
        step boundary. The step and its phases are ``engine.*`` spans
        (:class:`repro.obs.trace.Spans`)."""
        with self.spans("engine.step"):
            inj = self.faults
            held = 0
            if inj is not None:
                inj.straggle("step")
                k = inj.arena_pressure()
                if k:
                    held = self.alloc.hold_pages(k)
            try:
                admit_new = not (self.policy == "static"
                                 and self.sched.running)
                self.control_prefill(admit_new=admit_new)
                self.control_decode()
            finally:
                with self.spans("engine.commit"):
                    if held:
                        self.alloc.release_held()
                    if self.assert_invariants:
                        self.alloc.check()
                    self._step_gauges()

    def run(self) -> Dict:
        """Drain the queue; returns {summary, requests} telemetry.

        Every submitted request reaches a terminal status before this
        returns: ``finished`` (possibly ``truncated``) or ``shed`` --
        the no-silent-loss invariant the chaos suite asserts."""
        t0 = self.clock()
        iters = 0
        while self.sched.has_work:
            ts = self.clock()
            self.step()
            self.watchdog.observe(self.clock() - ts)
            iters += 1
            if iters > self.max_run_iters:
                raise RuntimeError(
                    "serving loop did not converge\n" + self._hang_report())
        wall = self.clock() - t0
        summary = summarize(self.requests, wall)
        # Deterministic structural metric alongside the wall-clock ones:
        # continuous batching's win IS fewer engine iterations for the same
        # token count (slot recycling), independent of host noise.
        summary["iterations"] = float(iters)
        # Robustness counters (all 0 on a fault-free engine) + step-latency
        # percentiles from the watchdog.
        # Counters read from the metrics registry (labels aggregated);
        # occupancy gauges contribute their run peaks (*_peak keys).
        summary["retries"] = self.metrics.value("retries")
        summary["fallbacks"] = self.metrics.value("fallbacks")
        summary["injected_faults"] = float(
            self.faults.total_injected if self.faults else 0)
        # KV-lifecycle counters (all 0 with both features off): prefill
        # positions actually computed, positions skipped via CoW prefix
        # hits, and the restore-vs-recompute restart split.
        for k in ("prefill_tokens", "prefix_hit_tokens", "offload_spills",
                  "offload_restores", "restarts_restored",
                  "restarts_recomputed"):
            summary[k] = self.metrics.value(k)
        summary.update(self.metrics.gauge_peaks())
        summary.update(self.watchdog.stats())
        report = {"summary": summary,
                  "requests": [self._req_report(r) for r in self.requests],
                  "quarantined": list(self.quarantined)}
        if self.faults is not None:
            report["faults"] = self.faults.report()
        return report

    def _hang_report(self, last_events: int = 32) -> str:
        """Diagnostic dump for a non-converging serving loop: scheduler
        queues, per-slot request states, allocator occupancy, robustness
        counters, and (when tracing is on) the last trace events -- so a
        hung engine is debuggable from the exception alone."""
        lines = ["-- engine hang diagnostics --"]
        q = [(r.rid, r.state, r.n_preempted, len(r.serve_prompt()))
             for r in self.sched.queue]
        lines.append(f"queue ({len(q)}): "
                     + ", ".join(f"rid={rid}[{st},pre={pre},len={ln}]"
                                 for rid, st, pre, ln in q[:16])
                     + (" ..." if len(q) > 16 else ""))
        for slot in sorted(self.sched.running):
            r = self.sched.running[slot]
            lines.append(
                f"slot {slot}: rid={r.rid} state={r.state} "
                f"cache_len={r.cache_len} prefill={r.prefill_pos}/"
                f"{r.prefill_target} gen={r.n_generated}/"
                f"{r.max_new_tokens} pages={len(self.alloc.slot_pages(slot))}")
        lines.append(
            f"allocator: {self.alloc.used_pages}/{self.alloc.n_pages} pages "
            f"used ({self.alloc.utilization:.0%}), "
            f"{self.alloc.held_pages} held, page_size={self.alloc.page_size}, "
            f"max_pages_per_seq={self.alloc.max_pages_per_seq}")
        lines.append(f"counters: {self.metrics.counters_flat()}")
        if self.tracer is not None:
            tail = self.tracer.tail(last_events)
            lines.append(f"last {len(tail)} trace events "
                         f"({self.tracer.dropped} dropped):")
            for ev in tail:
                lines.append(f"  {ev.get('ts', 0.0):>12.1f}us "
                             f"{ev.get('cat', '?')}/{ev.get('name', '?')} "
                             f"{ev.get('args', '')}")
        else:
            lines.append("tracing disabled (GEMMINI_TRACE / trace= would "
                         "append the last trace events here)")
        return "\n".join(lines)

    def _req_report(self, r: Request) -> Dict:
        itl = np.asarray(r.itl_s) if r.itl_s else None
        return {"rid": r.rid, "prompt_tokens": int(len(r.prompt)),
                "new_tokens": r.n_generated,
                "tokens": np.asarray(r.generated),
                "status": r.state, "shed_reason": r.shed_reason,
                "preempted": r.n_preempted, "truncated": r.truncated,
                "prefill_chunks": r.n_chunks,
                "ttft_s": (r.t_first_token - r.submitted_at)
                if r.t_first_token else None,
                "itl_p50_s": float(np.percentile(itl, 50))
                if itl is not None else None,
                "itl_p95_s": float(np.percentile(itl, 95))
                if itl is not None else None,
                "latency_s": (r.t_finished - r.submitted_at)
                if r.t_finished else None}

    # -- maintenance -------------------------------------------------------
    def defrag(self) -> None:
        """Compact live pages to the arena front (accounting only here;
        ``ServingEngine.defrag`` additionally permutes the device pools)."""
        self.alloc.defrag()


class ServingEngine(EngineControlPlane):
    """Continuous-batching executor for one model on one host.

    Knobs (see docs/serving.md for the policy discussion):

    * ``max_slots`` / ``max_context`` / ``page_size`` / ``n_pages`` --
      decode batch width and paged-arena geometry. ``page_size=None``
      resolves the tuned ``PagedAttnSchedule`` page size when
      ``GEMMINI_TUNE`` is not ``off``, else the static default.
    * ``backend`` -- ``xla`` (gather reference, exact-match contract),
      ``interpret`` (Pallas kernel bodies on CPU), ``pallas`` (TPU).
    * ``prefill_token_budget`` -- prefill cache positions per iteration.
    * ``prefill_chunk`` -- chunked prefill: ``None`` or negative =
      single-pass, ``0`` = auto (one page), else the chunk size in cache
      positions (floored to ``n_meta_tokens + 1``).
    * ``policy`` -- ``continuous``, or ``static`` (admission barrier, no
      slot recycling; the bench baseline). The barrier never blocks an
      in-flight chunked prefill, only new admissions.
    * ``admission_policy`` -- queue order for new admissions: ``fifo``
      (default, unchanged), ``priority`` (highest ``Request.priority``
      first, deadline then age break ties), or ``deadline``
      (earliest-deadline-first). See ``scheduler.ContinuousScheduler``.
    * ``warm_prompt_lens`` -- pre-resolve every tuned schedule the given
      prompt lengths will hit (no-op under ``GEMMINI_TUNE=off``).
    * ``faults`` / ``nan_guard`` / ``max_step_retries`` /
      ``retry_backoff_s`` / ``enforce_deadlines`` -- the robustness
      envelope (docs/serving.md#robustness): deterministic fault
      injection (``faults=None`` consults ``$GEMMINI_FAULTS``; off by
      default), post-step NaN/Inf guard with retry-on-the-XLA-twin +
      schedule quarantine (defaults to on iff faults are on), bounded
      retry-with-backoff for transient step failures, and SLO
      enforcement (shed expired deadlines instead of serving them).
    * ``assert_invariants`` -- debug oracle: run
      ``PagedKVAllocator.check()`` at every step boundary. Off by
      default; ``None`` consults ``$GEMMINI_CHECK``.
    * ``kv_offload`` / ``host_pool_pages`` / ``prefix_cache`` -- the
      page-granular KV lifecycle (docs/serving.md#kv-lifecycle), both off
      by default with bit-exact parity to the classic paths. Offload
      spills a preempted victim's committed pages to a host pool (LRU,
      ``host_pool_pages`` deep; default: the arena size) so restart is a
      DMA restore + resumed chunked prefill instead of a recompute; the
      prefix cache content-hashes full pages at prefill commit and maps
      shared prompt prefixes copy-on-write at admission (attention-only
      families -- an SSM's recurrent state cannot skip chunks).
    * ``watchdog`` -- a :class:`repro.runtime.StepWatchdog` (default: a
      fresh one) observing every engine iteration: straggler flags +
      step-latency percentiles in the run summary, optional heartbeat.
    * ``trace`` -- span tracing (docs/observability.md): ``None``
      consults ``$GEMMINI_TRACE`` (usually: off), ``True``/an int
      capacity/a :class:`repro.obs.trace.Tracer` enable the ring-buffered
      tracer for THIS engine (request lifecycle, step phases, allocator
      events). Off costs one None check per emission site; the disabled
      path is bit-exact against PR-7 (a regression test holds it there).
      The step's phase spans (``self.spans``: profiler annotations and
      registry observations) are on whatever ``trace`` says.
    * ``clock`` -- the engine's one monotonic clock (default
      ``time.monotonic``): every TTFT/ITL/latency/step duration and
      every trace timestamp derives from it, and ``submit(deadline=)``
      timestamps live in its domain (``engine.now() + rel_s``).
      Injectable for deterministic tests.

    Dispatch is an :class:`ExecutionContext` (``self.engine``): cfg +
    backend + tune policy in one frozen value handed to the jitted model
    steps. A mesh-aware context (``ExecutionContext.with_mesh``) is the
    multi-host path once the page arena itself is sequence-sharded
    (ROADMAP).
    """

    def __init__(self, model_cfg, *, max_slots: int = 4,
                 max_context: int = 2048,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 engine_cfg: Optional[GemminiConfig] = None,
                 backend: Optional[str] = None,
                 params=None, seed: int = 0,
                 temperature: float = 0.0,
                 prefill_token_budget: int = 512,
                 prefill_chunk: Optional[int] = None,
                 policy: str = "continuous",
                 admission_policy: str = "fifo",
                 warm_prompt_lens: Sequence[int] = (),
                 faults=None,
                 nan_guard: Optional[bool] = None,
                 max_step_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 enforce_deadlines: bool = False,
                 assert_invariants: Optional[bool] = None,
                 kv_offload: bool = False,
                 host_pool_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 watchdog: Optional[StepWatchdog] = None,
                 trace=None,
                 clock=None):
        super().__init__(model_cfg, max_slots=max_slots, policy=policy,
                         faults=faults, nan_guard=nan_guard,
                         max_step_retries=max_step_retries,
                         retry_backoff_s=retry_backoff_s,
                         assert_invariants=assert_invariants,
                         watchdog=watchdog, trace=trace, clock=clock)
        # Each phase span is also a profiler annotation, on the clock of
        # the device trace.
        self.spans.annotation = jax.profiler.TraceAnnotation
        self.temperature = temperature
        self.max_context = max_context
        cfg = engine_cfg or GemminiConfig(input_dtype="bf16",
                                          acc_dtype="fp32",
                                          output_dtype="bf16")
        self.engine = ExecutionContext(
            cfg=cfg, backend=backend or default_engine_backend())

        # -- page geometry: the tuned schedule is the page size ------------
        if page_size is None:
            if flags.get("tune_mode") != "off" and model_cfg.has_attn:
                from repro import tune
                page_size = tune.resolve_paged_attn_schedule(
                    cfg, max_slots, model_cfg.n_heads, model_cfg.n_kv_heads,
                    model_cfg.head_dim, max_context,
                    dtype=model_cfg.dtype).page_size
            else:
                from repro.tune.schedules import DEFAULT_PAGE_SIZE
                page_size = DEFAULT_PAGE_SIZE
        self.page_size = max(8, min(page_size, max_context))
        self.max_pages_per_seq = -(-max_context // self.page_size)
        if n_pages is None:
            # Budget-derived arena, capped at what the engine can ever hold
            # live: pages belong only to running slots, each at most
            # max_pages_per_seq deep, so anything beyond slots*MP is zero
            # pools that no schedule could touch (a full gemma3 config
            # would otherwise allocate the whole 4096-page cap -- GiBs of
            # zeros -- to serve a 2-request smoke batch).
            n_pages = max(self.max_pages_per_seq,
                          min(max_slots * self.max_pages_per_seq,
                              arena_pages(model_cfg, cfg, self.page_size)))
        # -- KV lifecycle (docs/serving.md#kv-lifecycle) -------------------
        self.kv_offload = bool(kv_offload)
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache and model_cfg.has_ssm:
            # A prefix hit skips the chunks below the anchor, but an
            # SSM/hybrid family's recurrent state is a function of every
            # skipped position -- CoW pages cannot carry it.
            raise ValueError("prefix_cache requires an attention-only "
                             f"family; {model_cfg.name!r} has SSM state")
        self.alloc = PagedKVAllocator(
            n_pages, self.page_size, self.max_pages_per_seq,
            tracer=self.tracer,
            host_pool_pages=((host_pool_pages if host_pool_pages is not None
                              else n_pages) if self.kv_offload else 0))
        # Prompt bucketing (compile-cache friendliness): legal only for
        # pure-attention families, where padded positions are provably dead
        # under the causal mask + length mask. An SSM/hybrid model's
        # recurrent scan state WOULD absorb padding tokens, silently
        # diverging from the reference path, so those prefill at exact
        # length (one compile per distinct prompt length).
        self.prefill_pad = 1 if model_cfg.has_ssm else self.page_size
        # Chunked prefill: None or negative = single-pass (classic; the
        # CLI's -1 convention works here too); 0 = auto (one page, the
        # natural page-multiple default); positive values are floored to
        # meta+1 by the scheduler (the first chunk carries the meta-token
        # prefix).
        if prefill_chunk is not None and prefill_chunk < 0:
            prefill_chunk = None
        elif prefill_chunk == 0:
            prefill_chunk = self.page_size
        self.sched = ContinuousScheduler(
            self.alloc, max_slots,
            prefill_token_budget=prefill_token_budget,
            extra_tokens_per_prefill=model_cfg.n_meta_tokens,
            pad_to=self.prefill_pad,
            prefill_chunk=prefill_chunk,
            admission_policy=admission_policy,
            enforce_deadlines=enforce_deadlines,
            clock=self.clock, tracer=self.tracer, metrics=self.metrics,
            offload=self.kv_offload, prefix_cache=self.prefix_cache,
            spill_fn=self._spill, restore_fn=self._restore)
        self.prefill_chunk = self.sched.prefill_chunk
        if policy == "static":
            # Static batching as a degenerate policy: admit only into an
            # EMPTY engine (group barrier, no slot recycling) and ignore
            # the prefill budget -- the whole group prefills at once.
            self.sched.prefill_token_budget = 1 << 30

        # -- model state + jitted steps ------------------------------------
        self._key = jax.random.PRNGKey(seed)
        if params is None:
            self._key, pk = jax.random.split(self._key)
            params = tf.init_params(pk, model_cfg)
        self.params = params
        self.state = tf.init_paged_state(model_cfg, max_slots, n_pages,
                                         self.page_size,
                                         self.max_pages_per_seq,
                                         dtype=model_cfg.dtype)
        mc = model_cfg
        # Guarded engines use non-donating jits (see _jitted_steps: the
        # XLA-twin retry needs the pre-call state buffer alive).
        self._steps = _jitted_steps(self.engine, mc, self.page_size,
                                    donate=not self.nan_guard)
        self._fb_steps = None        # XLA-twin fallbacks, built on demand
        # The tuned schedule the decode path launches, for quarantine on a
        # guard trip: the same key resolve_paged_attn_schedule resolved the
        # page size under. None when tuning is off or the family has no
        # attention (nothing tuned to quarantine).
        if mc.has_attn and flags.get("tune_mode") != "off":
            from repro.tune import schedules as tsched
            self._paged_sched_key = tsched.paged_attn_cache_key(
                cfg, max_slots, mc.n_heads, mc.n_kv_heads, mc.head_dim,
                max_context, window=None, dtype=mc.dtype)

        tok_shape = (max_slots,) if mc.n_codebooks == 1 \
            else (max_slots, mc.n_codebooks)
        self._next_token = np.zeros(tok_shape, np.int32)
        self.warm_stats: Optional[Dict[str, int]] = None
        if warm_prompt_lens and flags.get("tune_mode") != "off":
            self.warm_stats = self.warm(warm_prompt_lens)

    # -- plan warm-up ------------------------------------------------------
    def warm(self, prompt_lens: Sequence[int]) -> Dict[str, int]:
        """Pre-resolve every schedule the engine will launch: prefill GEMM
        and attention shapes per prompt bucket (batch 1), decode GEMMs at
        the slot batch, and the paged-attention page size the pools were
        sized with -- so no request ever tunes on the request path.

        With chunked prefill on, the buckets are *chunk lengths*, not
        prompt buckets: the first chunk prefills like a short fresh prompt
        (self-attention + GEMMs at the chunk length), continuation chunks
        launch only GEMMs -- their attention is the block-table gather
        kernel, whose tuned schedule IS the page size the pools were
        already sized with."""
        from repro import tune
        totals: Dict[str, int] = {}
        # Prefill really runs at bucket + meta tokens (embed_inputs prepends
        # them), so that is the length to warm -- warming the bare bucket
        # would populate fingerprints the request path never hits.
        first, rest = set(), set()
        for p in prompt_lens:
            dummy = Request(rid=-1,
                            prompt=np.zeros((max(1, int(p)),), np.int32),
                            max_new_tokens=0)
            spans = self.sched._chunk_spans(dummy)
            first.add(spans[0][2])
            for (s, _e, pe) in spans[1:]:
                rest.add(pe - s)
        for i, b in enumerate(sorted(first)):
            st = tune.warm_model_plans(
                self.engine.cfg, self.model_cfg, 1, b,
                include_decode=False,
                paged_slots=self.max_slots if i == 0 else 0,
                paged_max_context=self.max_context)
            totals = {k: totals.get(k, 0) + v for k, v in st.items()}
        for b in sorted(rest - first):
            st = tune.warm_model_plans(self.engine.cfg, self.model_cfg, 1, b,
                                       include_decode=False,
                                       include_attention=False)
            totals = {k: totals.get(k, 0) + v for k, v in st.items()}
        st = tune.warm_model_plans(self.engine.cfg, self.model_cfg,
                                   self.max_slots, 1,
                                   include_attention=False)
        totals = {k: totals.get(k, 0) + v for k, v in st.items()}
        return totals

    # -- sampling ----------------------------------------------------------
    def _sample(self, logits: jnp.ndarray) -> np.ndarray:
        """logits: (..., V) -> token ids, greedy unless temperature > 0."""
        if self.temperature <= 0:
            return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self._key, k = jax.random.split(self._key)
        return np.asarray(jax.random.categorical(
            k, logits / self.temperature), np.int32)

    # -- device state ------------------------------------------------------
    def _table_row(self, slot: int) -> np.ndarray:
        row = np.zeros((self.max_pages_per_seq,), np.int32)
        pages = self.alloc.slot_pages(slot)
        row[:len(pages)] = pages
        return row

    def _sync_tables(self, slots) -> None:
        tables = self.state.tables
        for slot in slots:
            tables = tables.at[slot].set(jnp.asarray(self._table_row(slot)))
        self.state = self.state._replace(tables=tables)

    # -- KV lifecycle compute hooks ----------------------------------------
    def _capture_spill(self, req: Request, page_ids: List[int]) -> Dict:
        """Device->host copy of a preemption victim's committed pages (plus
        its per-slot recurrent state); ``np.asarray`` forces the copy to
        complete while contents are still exclusively owned."""
        idx = jnp.asarray(np.asarray(page_ids, np.int64))
        st = self.state
        payload: Dict = {}
        if st.kv_k is not None:
            payload["kv_k"] = np.asarray(st.kv_k[:, :, idx])
            payload["kv_v"] = np.asarray(st.kv_v[:, :, idx])
        if st.conv is not None:
            payload["conv"] = np.asarray(st.conv[:, req.slot])
            payload["ssm"] = np.asarray(st.ssm[:, req.slot])
        return payload

    def _apply_restore(self, req: Request, slot: int, spill) -> None:
        """Host->device copy of a spilled victim's pages into the freshly
        allocated slot's pages."""
        pages = self.alloc.slot_pages(slot)[:spill.n_pages]
        idx = jnp.asarray(np.asarray(pages, np.int64))
        st = self.state
        pl = spill.payload
        if st.kv_k is not None:
            st = st._replace(
                kv_k=st.kv_k.at[:, :, idx].set(jnp.asarray(pl["kv_k"])),
                kv_v=st.kv_v.at[:, :, idx].set(jnp.asarray(pl["kv_v"])))
        if st.conv is not None:
            st = st._replace(
                conv=st.conv.at[:, slot].set(jnp.asarray(pl["conv"])),
                ssm=st.ssm.at[:, slot].set(jnp.asarray(pl["ssm"])))
        self.state = st

    # -- robustness envelope (compute side) --------------------------------
    def _fallback_steps(self):
        """The bit-exact XLA twins of the jitted steps (PR 3/4's exactness
        contract is what makes degraded mode *exact*): same model, same
        paged state, same engine datapath for every projection -- only the
        kernel lowerings swap for their plan-free XLA twins
        (``backend="xla_twin"``; the plain ``xla`` backend would also flip
        the model onto the float-LM projection path and the re-run would
        drift off the faulted stream at bf16-rounding level). An engine
        already lowering to XLA (``xla`` or ``xla_twin``) has no tuned
        schedule to blame, so its fallback is a clean re-run of the same
        backend (donate=False variant)."""
        if self._fb_steps is None:
            fb = self.engine.backend if self.engine.impl_backend == "xla" \
                else "xla_twin"
            self._fb_steps = _jitted_steps(
                self.engine.with_backend(fb), self.model_cfg,
                self.page_size, donate=False)
        return self._fb_steps

    def _dispatch(self, which: str, args: tuple):
        return self._steps[which](*args)

    def _dispatch_fallback(self, which: str, args: tuple):
        return self._fallback_steps()[which](*args)

    # -- trace-time audit hooks (repro.analysis.lint.jit_audit) ------------
    @staticmethod
    def _bucket_key(which: str, args: tuple):
        """The compile-bucket a dispatch lands in: the traced token-block
        shape plus any static argument (the chunk steps' kv_pages)."""
        if which in ("prefill", "prefill_nl"):
            return (int(args[1].shape[1]),)
        if which in ("chunk", "chunk_nl"):
            return (int(args[1].shape[1]), args[6])
        return ()                                    # decode: one bucket

    def jit_cache_stats(self) -> Dict[str, int]:
        """Observed compile-bucket counts per jitted step (both the
        primary steps and, once built, the XLA-twin fallbacks)."""
        out: Dict[str, int] = {}
        for label, steps in (("", self._steps),
                             ("fb:", self._fb_steps or {})):
            for which, fn in steps.items():
                try:
                    out[label + which] = int(fn._cache_size())
                except Exception:
                    pass
        return out

    def audit(self):
        """Run the trace-time lint audit against this live engine:
        compile-bucket explosions (GL601) and post-donation buffer reuse
        (GL602).  Returns the findings (empty list = healthy)."""
        from repro.analysis.lint import jit_audit
        return jit_audit.audit_engine(self)

    # -- execution compute hooks -------------------------------------------
    def _exec_chunk(self, w):
        """Execute one prefill chunk's device work.

        Single-span chunks (``first and last``) take the classic
        whole-prompt path unchanged. Otherwise: the first chunk runs the
        fresh ``paged_prefill`` (meta prefix, SSM state reset, self-only
        attention -- positions [0, chunk) see no cache); continuation
        chunks run ``paged_prefill_chunk`` (resume SSM state, attend cache
        pages + chunk at offset ``start``). Only the last chunk samples --
        its final row is the prompt's last true position -- and only then
        does the slot's device length go live, flipping it into the decode
        active set (the device table sync can wait until then: the chunk
        calls carry the table row as an argument, and a mid-prefill slot
        never decodes)."""
        req, slot = w.req, w.slot
        meta = self.model_cfg.n_meta_tokens
        prompt = req.serve_prompt()
        true_len = len(prompt) + meta
        with self.spans("engine.prep"):
            if w.first and w.last:
                toks = prompt
                pad = self._bucket(len(prompt)) - len(prompt)
            else:
                toks = prompt[max(0, w.start - meta): w.true_end - meta]
                pad = w.padded_end - w.true_end
            if pad:
                toks = np.pad(toks, ((0, pad),) + ((0, 0),)
                              * (toks.ndim - 1))
            args = (self.params, jnp.asarray(toks[None]), self.state,
                    jnp.int32(slot), jnp.asarray(self._table_row(slot)))
            if not w.first:
                # Static dead-key bound for the gather attention: the
                # scheduler stamps each continuation chunk with the pages
                # the whole (padded) prompt will ever occupy
                # (PrefillChunk.kv_pages) -- table entries past it can
                # never hold live keys and need not be contracted.
                args += (jnp.int32(w.start), w.kv_pages or None)
        if w.first:
            site, which = "prefill", "prefill" if w.last else "prefill_nl"
        else:
            site, which = "chunk", "chunk" if w.last else "chunk_nl"
        logits, self.state = self._run_guarded(site, which, args)
        if not w.last:
            return None
        with self.spans("engine.prep"):
            self.state = self.state._replace(
                lengths=self.state.lengths.at[slot].set(true_len))
            self._sync_tables([slot])
            rows = logits[0, (true_len - 1) - w.start]
        with self.spans("engine.wait"):
            return self._sample(rows)

    def _exec_decode(self, active_np: np.ndarray) -> np.ndarray:
        with self.spans("engine.prep"):
            toks = self._next_token[:, None] \
                if self.model_cfg.n_codebooks == 1 \
                else self._next_token[:, None, :]
            args = (self.params, jnp.asarray(toks), self.state,
                    jnp.asarray(active_np))
        logits, self.state = self._run_guarded("decode", "decode", args)
        # The sampled rows' slice queues behind the step without waiting;
        # the sampler then blocks until the device is done.
        with self.spans("engine.prep"):
            rows = logits[:, -1]
        with self.spans("engine.wait"):
            return self._sample(rows)

    # -- maintenance -------------------------------------------------------
    def defrag(self) -> None:
        """Compact live pages to the arena front: permute the device pools
        and rewrite every slot's table (see PagedKVAllocator.defrag)."""
        perm = self.alloc.defrag()
        if self.state.kv_k is not None:
            inv = np.argsort(perm)
            idx = jnp.asarray(np.concatenate([inv, [self.alloc.n_pages]]))
            self.state = self.state._replace(
                kv_k=jnp.take(self.state.kv_k, idx, axis=2),
                kv_v=jnp.take(self.state.kv_v, idx, axis=2))
        self._sync_tables(list(self.sched.running))
