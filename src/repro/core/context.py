"""ExecutionContext: one mesh-aware dispatch API for every engine op.

The paper's full-stack argument (kernels are only meaningful inside the
programming stack and system that launch them) previously leaked into our
code as plumbing: every layer threaded a stringly-typed ``backend=``
argument plus the implicit ``GEMMINI_TUNE`` process global through ~10
modules, and under jit+GSPMD the Pallas ops resolved tile plans at trace
time with the GLOBAL logical shape -- making the tuned-kernel path
single-host only.  :class:`ExecutionContext` owns all of that in one
frozen value:

  * ``cfg``       -- the elaborated :class:`GemminiConfig` the kernels
                     tile against (``None`` is legal for the attention
                     ops, which fall back to the bf16 engine default);
  * ``backend``   -- ``pallas`` | ``interpret`` | ``xla`` | ``xla_twin``,
                     chosen once instead of per call.  ``xla_twin`` is the
                     degraded-mode backend: every *kernel* dispatches its
                     plan-free XLA twin (bit-identical to the Pallas body,
                     no tuned schedule involved), but the model layers
                     still see a non-``xla`` backend and keep routing
                     projections through the engine datapath -- so a step
                     re-run on the twin after a fault is bit-exact against
                     the faulted engine's own step, which the plain
                     ``xla`` backend (float-LM projection path) is not;
  * ``tune_mode`` -- per-context override of the ``GEMMINI_TUNE`` flag
                     (``None`` inherits the process flag), scoped around
                     each dispatch so two contexts with different tune
                     policies can coexist in one process;
  * ``mesh`` / ``axis`` -- when set, every batched op is wrapped in
                     ``shard_map`` over the mesh's ``axis`` so the Pallas
                     kernel body AND its schedule resolution
                     (``_resolve_plan`` / ``_resolve_attn_blocks``) see
                     PER-DEVICE shapes.  This is what makes tuned Pallas
                     kernels legal inside a GSPMD-partitioned step, and
                     what ``tune.warm_model_plans(n_shards=...)`` warms:
                     exactly the shapes each device launches.

Ops are looked up in a registry, so ``ctx.gemm(...)``,
``ctx.flash_attention(...)``, ``ctx.conv2d(...)``, ``ctx.ssd(...)``,
``ctx.paged_attention(...)``, ``ctx.paged_prefill_attention(...)`` and
``ctx.matmul(...)`` all dispatch through the same mesh/tune/backend
policy; new ops join via :func:`register_op`.

The old ``repro.kernels.ops.*(backend=...)`` entry points are gone (their
one-release deprecation-shim grace period ended in PR 7); lint rule GL506
forbids rebinding the legacy names, and :class:`GemminiDeprecationWarning`
remains the class any future repro deprecation must emit (the test suite
escalates it to an error for in-tree callers).

Sharding semantics (the ``mesh`` wrap):

  * only the leading *batch-like* axis is partitioned (GEMM rows M,
    attention/conv/SSD batch B, paged-decode slots); weights, KV pools
    and other broadcast operands are replicated -- this mirrors the
    data-parallel request path the launchers run;
  * the wrap applies only to the ``pallas`` / ``interpret`` backends.
    The ``xla`` reference is plan-free and SPMD-partitionable by
    construction, so the GSPMD partitioner (not shard_map) remains the
    right tool there and ``mesh`` is ignored;
  * a batch axis not divisible by the mesh axis falls back to the
    unsharded dispatch (same divisibility-or-replicate philosophy as
    ``launch.sharding``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

from repro.core import flags
from repro.core.config import GemminiConfig

BACKENDS = ("xla", "pallas", "interpret", "xla_twin")


class GemminiDeprecationWarning(DeprecationWarning):
    """Deprecated repro API surface (the pre-ExecutionContext op entries).

    A distinct subclass so the test suite can escalate exactly our own
    deprecations to errors (``pytest.ini``) without tripping on
    unrelated DeprecationWarnings from jax/numpy.
    """


# ---------------------------------------------------------------------------
# op registry
# ---------------------------------------------------------------------------
_OPS: Dict[str, Callable] = {}


def register_op(name: str) -> Callable[[Callable], Callable]:
    """Register ``fn(ctx, *args, **kw)`` as the dispatch for ``ctx.<name>``.

    The registry is how the context stays open for extension: a new kernel
    class adds one impl + one ``register_op`` call and every context
    (mesh'd or not) can launch it.
    """

    def deco(fn: Callable) -> Callable:
        if name in _OPS:
            raise ValueError(f"op {name!r} already registered")
        _OPS[name] = fn
        return fn

    return deco


def registered_ops() -> Tuple[str, ...]:
    return tuple(sorted(_OPS))


# ---------------------------------------------------------------------------
# the context
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecutionContext:
    """Backend + tune policy + partitioning for every engine op.

    Frozen and hashable: a context is a *value* (jit caches and the
    serving engine key on it), and derived contexts come from
    :meth:`with_backend` / :meth:`with_mesh` rather than mutation.
    """

    cfg: Optional[GemminiConfig] = None
    backend: str = "xla"
    tune_mode: Optional[str] = None     # None = inherit the process flag
    mesh: Any = None                    # jax.sharding.Mesh or None
    axis: Any = "data"                  # mesh axis name (or tuple of names)

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"have {BACKENDS}")
        if self.tune_mode is not None and \
                self.tune_mode not in flags.TUNE_MODES:
            raise ValueError(f"tune_mode must be None or one of "
                             f"{flags.TUNE_MODES}, got {self.tune_mode!r}")
        if self.mesh is not None:
            names = self.axis if isinstance(self.axis, tuple) else (self.axis,)
            missing = [a for a in names if a not in self.mesh.axis_names]
            if missing:
                raise ValueError(f"axis {missing} not in mesh axes "
                                 f"{self.mesh.axis_names}")

    # -- derivation --------------------------------------------------------
    def with_backend(self, backend: str) -> "ExecutionContext":
        return dataclasses.replace(self, backend=backend)

    def with_mesh(self, mesh, axis: Any = "data") -> "ExecutionContext":
        return dataclasses.replace(self, mesh=mesh, axis=axis)

    def unsharded(self) -> "ExecutionContext":
        """The same context without the mesh (single-host dispatch)."""
        return dataclasses.replace(self, mesh=None)

    def with_tune_mode(self, tune_mode: Optional[str]) -> "ExecutionContext":
        return dataclasses.replace(self, tune_mode=tune_mode)

    # -- mesh introspection ------------------------------------------------
    @property
    def n_shards(self) -> int:
        """Devices along ``axis`` (1 without a mesh) -- the divisor
        per-device batch shapes are warmed with
        (``tune.warm_model_plans(n_shards=...)``)."""
        if self.mesh is None:
            return 1
        names = self.axis if isinstance(self.axis, tuple) else (self.axis,)
        n = 1
        for a in names:
            n *= self.mesh.shape[a]
        return n

    @property
    def impl_backend(self) -> str:
        """The kernel-impl dispatch string: ``xla_twin`` lowers every op
        to its plan-free XLA twin (``backend="xla"`` at the impl layer)
        while remaining a distinct *model-level* backend -- see the class
        docstring for why the twin must not take the float-LM projection
        shortcut."""
        return "xla" if self.backend == "xla_twin" else self.backend

    @property
    def sharded(self) -> bool:
        """True when dispatch wraps kernels in shard_map: a mesh of more
        than one device is set AND the backend runs real kernel bodies
        (the xla reference and the xla_twin are already
        SPMD-partitionable; GSPMD owns them). A Mosaic kernel cannot be
        partitioned automatically, so the wrap applies even when the
        batch axis has one device (a ``(data=1, model=N)`` mesh): the
        kernel then runs whole on every device."""
        return self.mesh is not None and self.impl_backend != "xla" \
            and self.mesh.size > 1

    # -- dispatch ----------------------------------------------------------
    @contextlib.contextmanager
    def _tune_scope(self):
        """Apply this context's tune policy for the duration of one
        dispatch (trace-time: schedule resolution happens while tracing),
        restoring the process flag afterwards."""
        if self.tune_mode is None or self.tune_mode == flags.get("tune_mode"):
            yield
            return
        prev = flags.get("tune_mode")
        flags.set_flag("tune_mode", self.tune_mode)
        try:
            yield
        finally:
            flags.set_flag("tune_mode", prev)

    def _shard_call(self, fn: Callable, arrays: Tuple, batched: Tuple[bool, ...],
                    out_batched: Any = True):
        """Run ``fn(*arrays)`` under shard_map, dim 0 of each batched
        array partitioned over ``self.axis`` (others replicated), so the
        kernel and its schedule resolution see per-device shapes.

        ``out_batched``: pytree-prefix of bools for the outputs (True =
        dim 0 partitioned). Falls back to the plain call when any batched
        dim does not divide the mesh axis.
        """
        import jax
        from jax.sharding import PartitionSpec as P
        n = self.n_shards
        if not self.sharded or any(
                b and (a.shape[0] % n != 0 or a.shape[0] < n)
                for a, b in zip(arrays, batched)):
            return fn(*arrays)
        bspec = P(self.axis)
        in_specs = tuple(bspec if b else P() for b in batched)

        def out_spec(b):
            return bspec if b else P()

        wrapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=jax.tree.map(out_spec, out_batched),
            check_vma=False)
        return wrapped(*arrays)

    def __getattr__(self, name: str):
        # Only consulted for attributes not found normally: op dispatch.
        if name.startswith("_") or name not in _OPS:
            raise AttributeError(
                f"ExecutionContext has no op {name!r}; registered ops: "
                f"{registered_ops()}")
        fn = functools.partial(_OPS[name], self)
        inj = _fault_injector()
        return fn if inj is None else _faulted_op(name, fn, inj)


def _tracing() -> bool:
    """True while JAX traces (jit, grad, scan bodies): the fault hook below
    then passes through, since their host-side work would be baked into the
    compiled program or would block on tracers."""
    import jax
    return not jax.core.trace_ctx.is_top_level()


def _fault_injector():
    """The process-global fault injector, if one is installed (see
    :mod:`repro.runtime.faults`). Lazy import: core must not depend on
    runtime at import time, and the common case (no faults) costs one
    None check per dispatch."""
    try:
        from repro.runtime import faults
    except ImportError:                       # pragma: no cover - stub envs
        return None
    return faults.active()


def _faulted_op(name: str, fn: Callable, inj) -> Callable:
    """Wrap one op dispatch with the injector's op-boundary hooks at site
    ``op:<name>``: a transient spec raises before the call, a poison spec
    NaN/Inf-fills the (first) output after it.

    Injection applies only to EAGER calls. Under a jit trace the wrapper
    is a pass-through: a fault injected at trace time would be baked into
    the compiled function -- permanent, unseedable, and invisible to the
    engine's host-level guards -- so traced ops fault at the engine's
    step boundaries instead (see ServingEngine._run_guarded)."""

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        if _tracing():
            return fn(*args, **kw)
        site = f"op:{name}"
        inj.check_transient(site)
        out = fn(*args, **kw)
        if isinstance(out, tuple):
            return (inj.poison(site, out[0]),) + out[1:]
        return inj.poison(site, out)

    return wrapped


@functools.lru_cache(maxsize=1)
def default_context() -> ExecutionContext:
    """The plan-free XLA reference context (what ops ran with before a
    caller ever chose a backend)."""
    return ExecutionContext(cfg=None, backend="xla")


def as_context(obj: Any) -> ExecutionContext:
    """Normalize the model zoo's dispatch argument: an
    :class:`ExecutionContext` passes through, an elaborated
    ``GemminiInstance`` contributes its ``.ctx``, and ``None`` means the
    default XLA reference context."""
    if obj is None:
        return default_context()
    if isinstance(obj, ExecutionContext):
        return obj
    ctx = getattr(obj, "ctx", None)
    if isinstance(ctx, ExecutionContext):
        return ctx
    raise TypeError(f"cannot derive an ExecutionContext from {type(obj)!r}")


def _require_cfg(ctx: ExecutionContext, op: str) -> GemminiConfig:
    if ctx.cfg is None:
        raise ValueError(f"ctx.{op} needs an elaborated GemminiConfig; "
                         f"this context has cfg=None (the attention ops "
                         f"accept that, the engine ops do not)")
    return ctx.cfg


# ---------------------------------------------------------------------------
# registered ops (thin policy wrappers over kernels.ops *_impl entries)
# ---------------------------------------------------------------------------
@register_op("gemm")
def _gemm(ctx: ExecutionContext, a, b, d=None, **kw):
    """C = act(round_shift(A @ B + D)); under a mesh the GEMM's M rows are
    partitioned so each device resolves (and launches) the per-device
    plan. ``b`` may be an (S, K, N) stack with ``layer=`` its index (the
    weights stay replicated either way). See
    :func:`repro.kernels.ops.gemm_impl` for the backend x tune-mode
    matrix."""
    from repro.kernels import ops
    cfg = _require_cfg(ctx, "gemm")
    with ctx._tune_scope():
        m = a.shape[0]
        if d is None or not ctx.sharded or m % ctx.n_shards or \
                m < ctx.n_shards:
            # Unsharded dispatch (or no bias): hand d through untouched --
            # the impl owns its (1|M, N) broadcast/padding exactly as
            # before the context existed.
            return ctx._shard_call(
                lambda aa, bb: ops.gemm_impl(aa, bb, d, cfg=cfg,
                                             backend=ctx.impl_backend, **kw),
                (a, b), (True, False))
        import jax.numpy as jnp
        # Sharded + biased: a broadcast (1, N) bias row cannot shard over
        # M, so materialize it to the M rows only HERE, where each device
        # must see its own slice (the engine kernel streams a full (M, N)
        # D operand either way).
        db = jnp.broadcast_to(d, (m, b.shape[-1]))
        return ctx._shard_call(
            lambda aa, bb, dd: ops.gemm_impl(aa, bb, dd, cfg=cfg,
                                             backend=ctx.impl_backend, **kw),
            (a, b, db), (True, False, True))


@register_op("matmul")
def _matmul(ctx: ExecutionContext, a, b, **kw):
    """Batched-LHS matmul sugar over ``ctx.gemm`` (M = prod of leading
    dims; the flattened rows are what a mesh partitions)."""
    lead = a.shape[:-1]
    y = _gemm(ctx, a.reshape(-1, a.shape[-1]), b, **kw)
    return y.reshape(*lead, b.shape[-1])


@register_op("conv2d")
def _conv2d(ctx: ExecutionContext, x, w, b=None, **kw):
    """Conv2D on the GEMM engine; under a mesh the image batch N is
    partitioned (weights/bias replicated). See
    :func:`repro.kernels.ops.conv2d_impl` for the backend x fused
    matrix."""
    from repro.kernels import ops
    cfg = _require_cfg(ctx, "conv2d")
    with ctx._tune_scope():
        return ctx._shard_call(
            lambda xx: ops.conv2d_impl(xx, w, b, cfg=cfg,
                                       backend=ctx.impl_backend, **kw),
            (x,), (True,))


@register_op("flash_attention")
def _flash_attention(ctx: ExecutionContext, q, k, v, **kw):
    """Blockwise-softmax attention; under a mesh the batch B is
    partitioned, so ``_resolve_attn_blocks`` fingerprints the per-device
    batch (the shape ``warm_model_plans(n_shards=...)`` warms). See
    :func:`repro.kernels.ops.flash_attention_impl`."""
    from repro.kernels import ops
    with ctx._tune_scope():
        return ctx._shard_call(
            lambda qq, kk, vv: ops.flash_attention_impl(
                qq, kk, vv, cfg=ctx.cfg, backend=ctx.impl_backend, **kw),
            (q, k, v), (True, True, True))


@register_op("paged_attention")
def _paged_attention(ctx: ExecutionContext, q, k_pool, v_pool, block_tables,
                     lengths, layer, **kw):
    """Paged-KV single-token decode; under a mesh the decode *slots* are
    partitioned (each device attends its slots against the replicated
    stacked page pools -- the sequence-sharded arena is the ROADMAP
    follow-on).
    See :func:`repro.kernels.ops.paged_attention_impl`."""
    from repro.kernels import ops
    with ctx._tune_scope():
        return ctx._shard_call(
            lambda qq, bt, ln: ops.paged_attention_impl(
                qq, k_pool, v_pool, bt, ln, layer, cfg=ctx.cfg,
                backend=ctx.impl_backend, **kw),
            (q, block_tables, lengths), (True, True, True))


@register_op("paged_prefill_attention")
def _paged_prefill_attention(ctx: ExecutionContext, q, k_pool, v_pool,
                             block_table, start, layer, **kw):
    """Chunked-prefill attention over a paged cache. Per-request by
    construction (B == 1), so there is no batch axis to partition and the
    mesh never wraps it; on a sharded engine it runs replicated inside
    the surrounding step. See
    :func:`repro.kernels.ops.paged_prefill_attention_impl`."""
    from repro.kernels import ops
    with ctx._tune_scope():
        return ops.paged_prefill_attention_impl(
            q, k_pool, v_pool, block_table, start, layer,
            backend=ctx.impl_backend, **kw)


@register_op("ssd")
def _ssd(ctx: ExecutionContext, x, dt, a_log, b, c, **kw):
    """Mamba-2 SSD mixer; under a mesh the batch B is partitioned
    (``a_log``/``d_skip`` replicated). See
    :func:`repro.kernels.ops.ssd_impl` for the backend matrix and the
    ``initial_state`` / ``return_final_state`` resume contract."""
    from repro.kernels import ops
    with ctx._tune_scope():
        init = kw.get("initial_state")
        out_batched = (True, True) if kw.get("return_final_state") else True
        if init is not None:
            kw = dict(kw)
            del kw["initial_state"]
            return ctx._shard_call(
                lambda xx, dd, bb, cc, ii: ops.ssd_impl(
                    xx, dd, a_log, bb, cc, initial_state=ii,
                    backend=ctx.impl_backend, **kw),
                (x, dt, b, c, init), (True,) * 5, out_batched)
        return ctx._shard_call(
            lambda xx, dd, bb, cc: ops.ssd_impl(
                xx, dd, a_log, bb, cc, backend=ctx.impl_backend, **kw),
            (x, dt, b, c), (True,) * 4, out_batched)
