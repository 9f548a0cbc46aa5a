"""ExecutionContext dispatch: registry, tune-mode scoping, deprecation
shims, the SSD fused epilogue/final-state contract, and the chunked-gather
kv_pages static bound. The mesh'd (shard_map) path is covered by the
multi-device subprocess test in test_sharding_dryrun.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import context, flags
from repro.core.config import Activation, GemminiConfig
from repro.core.context import ExecutionContext
from repro.core.generator import elaborate
from repro.kernels import ops, ref
from repro.models import ssm


@pytest.fixture(autouse=True)
def _reset_flags():
    flags.reset()
    yield
    flags.reset()


def _ints(rng, shape, lo=-128, hi=128, dtype=jnp.int8):
    return jnp.asarray(rng.integers(lo, hi, shape), dtype)


# ---------------------------------------------------------------------------
# construction / registry
# ---------------------------------------------------------------------------
def test_context_validates_fields():
    with pytest.raises(ValueError):
        ExecutionContext(backend="mosaic")
    with pytest.raises(ValueError):
        ExecutionContext(tune_mode="sometimes")


def test_context_is_hashable_value():
    a = ExecutionContext(cfg=GemminiConfig(), backend="interpret")
    b = ExecutionContext(cfg=GemminiConfig(), backend="interpret")
    assert a == b and hash(a) == hash(b)
    assert a.with_backend("xla") != a


def test_registry_lists_every_op_and_rejects_unknown():
    ctx = ExecutionContext()
    have = context.registered_ops()
    for op in ("gemm", "matmul", "conv2d", "flash_attention",
               "paged_attention", "paged_prefill_attention", "ssd"):
        assert op in have
        assert callable(getattr(ctx, op))
    with pytest.raises(AttributeError):
        ctx.winograd
    with pytest.raises(ValueError):
        context.register_op("gemm")(lambda ctx: None)   # duplicate


def test_engine_ops_require_cfg():
    with pytest.raises(ValueError):
        ExecutionContext(backend="interpret").gemm(
            jnp.zeros((8, 8), jnp.int8), jnp.zeros((8, 8), jnp.int8))


def test_as_context_protocol():
    inst = elaborate(GemminiConfig(), "interpret")
    assert context.as_context(inst) is inst.ctx
    ctx = ExecutionContext(backend="xla")
    assert context.as_context(ctx) is ctx
    assert context.as_context(None).backend == "xla"
    with pytest.raises(TypeError):
        context.as_context(object())


def test_instance_with_mesh_derives_ctx():
    inst = elaborate(GemminiConfig(), "interpret")
    mesh = jax.make_mesh((1,), ("data",))
    m = inst.with_mesh(mesh)
    assert m.ctx.mesh is mesh and m.ctx.n_shards == 1
    assert not m.ctx.sharded                   # 1 shard: plain dispatch
    assert inst.ctx.mesh is None               # original untouched


# ---------------------------------------------------------------------------
# numerics: ctx dispatch == kernel impls == refs
# ---------------------------------------------------------------------------
def test_ctx_gemm_matches_ref(rng):
    cfg = GemminiConfig()
    ctx = ExecutionContext(cfg=cfg, backend="interpret")
    a, b = _ints(rng, (100, 72)), _ints(rng, (72, 40))
    d = _ints(rng, (1, 40), -500, 500, jnp.int32)
    y = ctx.gemm(a, b, d, shift=7, activation=Activation.RELU)
    yr = ref.gemm_ref(a, b, d, acc_dtype=jnp.int32, out_dtype=jnp.int8,
                      shift=7, activation=Activation.RELU)
    assert bool(jnp.all(y == yr))


def test_ctx_flash_attention_default_cfg(rng):
    """cfg=None is legal for the attention ops (bf16 engine default)."""
    ctx = ExecutionContext(backend="interpret")
    q = jnp.asarray(rng.standard_normal((1, 64, 4, 16)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
    y = ctx.flash_attention(q, kv, kv, causal=True)
    yr = ctx.with_backend("xla").flash_attention(q, kv, kv, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


def test_ctx_tune_mode_scoped_per_dispatch(rng, tmp_path):
    """ctx.tune_mode overrides the process flag only for the dispatch:
    the cached-mode context consults the plan cache while the process
    stays in off mode before and after."""
    flags.set_flag("tune_cache", str(tmp_path / "plans.json"))
    from repro.tune import cache as tcache
    tcache.reset_cache()
    cfg = GemminiConfig()
    a, b = _ints(rng, (64, 64)), _ints(rng, (64, 64))
    assert flags.get("tune_mode") == "off"
    pc = tcache.get_cache()
    m0 = pc.misses
    ctx = ExecutionContext(cfg=cfg, backend="interpret", tune_mode="cached")
    y = ctx.gemm(a, b, None, shift=4)
    assert pc.misses == m0 + 1            # the cache WAS consulted
    assert flags.get("tune_mode") == "off"   # scope restored
    off = ExecutionContext(cfg=cfg, backend="interpret", tune_mode="off")
    assert bool(jnp.all(off.gemm(a, b, None, shift=4) == y))
    tcache.reset_cache()


# ---------------------------------------------------------------------------
# the old ops.*(backend=...) shims are GONE (PR 7, grace period over)
# ---------------------------------------------------------------------------
def test_legacy_shims_removed(rng):
    """The seven PR-5 deprecation shims no longer exist on ops; the
    *_impl entries (the ExecutionContext dispatch surface) remain, and
    lint rule GL506 forbids rebinding the legacy names."""
    for name in ("gemm", "matmul", "conv2d", "flash_attention",
                 "paged_attention", "paged_prefill_attention", "ssd"):
        assert not hasattr(ops, name), f"legacy shim ops.{name} resurfaced"
        assert hasattr(ops, name + "_impl")
    # the impl surface stays warning-free and live
    cfg = GemminiConfig(input_dtype="fp32", acc_dtype="fp32",
                        output_dtype="fp32")
    a = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    ops.gemm_impl(a, b, cfg=cfg)


# ---------------------------------------------------------------------------
# ssd: fused epilogue / final state / initial_state demotion
# ---------------------------------------------------------------------------
def _ssd_inputs(rng, bsz=1, t=48, h=2, p=8, g=1, n=16):
    x = jnp.asarray(rng.standard_normal((bsz, t, h, p)), jnp.float32)
    dt = jnp.abs(jnp.asarray(rng.standard_normal((bsz, t, h)) * 0.5,
                             jnp.float32)) + 0.01
    a_log = jnp.asarray(rng.standard_normal((h,)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((bsz, t, g, n)) * 0.3, jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, t, g, n)) * 0.3, jnp.float32)
    d_skip = jnp.asarray(rng.standard_normal((h,)) * 0.5, jnp.float32)
    return x, dt, a_log, b, c, d_skip


def test_ctx_ssd_kernel_final_state_fused(rng):
    """The interpret path returns the kernel-emitted final state (no XLA
    recompute) and it matches the reference handoff state."""
    x, dt, a_log, b, c, d_skip = _ssd_inputs(rng)
    ctx = ExecutionContext(backend="interpret")
    y, fs = ctx.ssd(x, dt, a_log, b, c, d_skip=d_skip, chunk=16,
                    return_final_state=True)
    y_ref, fs_ref = ctx.with_backend("xla").ssd(
        x, dt, a_log, b, c, d_skip=d_skip, chunk=16,
        return_final_state=True)
    rel = float(jnp.max(jnp.abs(y - y_ref))) / float(jnp.max(jnp.abs(y_ref)))
    assert rel < 1e-4
    np.testing.assert_allclose(np.asarray(fs), np.asarray(fs_ref),
                               rtol=1e-4, atol=1e-5)


def test_ssd_kernel_is_single_pallas_call_with_fused_epilogue(rng):
    """Fusion audit acceptance: one pallas_call lowers the whole SSD --
    d_skip epilogue and final-state emission included; no post-kernel
    XLA add/recompute pass."""
    x, dt, a_log, b, c, d_skip = _ssd_inputs(rng)

    def run(x, dt, b, c):
        return ops.ssd_impl(x, dt, a_log, b, c, d_skip=d_skip, chunk=16,
                            backend="interpret", return_final_state=True)

    jaxpr = jax.make_jaxpr(run)(x, dt, b, c)
    flat = jaxpr.jaxpr
    n_calls = sum(1 for e in flat.eqns if "pallas_call" in str(e.primitive))
    assert n_calls == 1
    # no einsum/dot epilogue after the kernel: every dot lives in-kernel
    assert not any("dot_general" in str(e.primitive) for e in flat.eqns)


def test_ctx_ssd_initial_state_demotes_to_xla(rng):
    """A resumed chunk (initial_state != None) runs the xla reference on
    every backend -- bit-identical to calling the reference directly."""
    x, dt, a_log, b, c, d_skip = _ssd_inputs(rng, t=32)
    init = jnp.asarray(rng.standard_normal((1, 2, 16, 8)), jnp.float32)
    ctx = ExecutionContext(backend="interpret")
    y = ctx.ssd(x, dt, a_log, b, c, d_skip=d_skip, chunk=16,
                initial_state=init)
    yr = ssm.ssd_chunked_xla(x, dt, a_log, b, c, d_skip=d_skip, chunk=16,
                             initial_state=init)
    assert bool(jnp.all(y == yr))


# ---------------------------------------------------------------------------
# chunked-gather kv_pages static bound
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_paged_prefill_kv_pages_bound_exact(rng, backend):
    """Slicing the table to the admission-time page bound is a pure
    dead-key elision: output exactly matches the capacity-wide gather."""
    h, kvh, d, page, mp = 4, 2, 16, 8, 12
    start, tq = 8, 8                          # chunk 2 of a 16-token prompt
    kv_pages = 2                              # covers start + tq = 16 keys
    pool_shape = (1, kvh, mp + 1, page, d)        # a one-layer stack
    k_pool = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    table = jnp.asarray(rng.permutation(mp).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((1, tq, h, d)), jnp.float32)
    ctx = ExecutionContext(backend=backend)
    full = ctx.paged_prefill_attention(q, k_pool, v_pool, table,
                                       jnp.int32(start), 0)
    tight = ctx.paged_prefill_attention(q, k_pool, v_pool, table,
                                        jnp.int32(start), 0,
                                        kv_pages=kv_pages)
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(full))


def test_paged_prefill_kv_pages_cuts_gathered_keys():
    """The xla twin's gather really shrinks: the contracted key axis is
    the 128-clamped kv_pages * page width, not the table capacity."""
    h, kvh, d, page, mp = 2, 1, 8, 8, 32     # capacity 256 keys
    pool = jnp.zeros((1, kvh, mp + 1, page, d), jnp.float32)
    table = jnp.arange(mp, dtype=jnp.int32)
    q = jnp.zeros((1, 8, h, d), jnp.float32)
    ctx = ExecutionContext(backend="xla")

    def width(kv_pages):
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: ctx.paged_prefill_attention(
                q, k, v, table, jnp.int32(0), 0, kv_pages=kv_pages))(
            q, pool, pool)
        # widest KV-shaped intermediate = the gathered/padded key axis
        return max(v.aval.shape[1] for e in jaxpr.jaxpr.eqns
                   for v in e.outvars
                   if len(v.aval.shape) == 4 and v.aval.shape[0] == 1
                   and v.aval.shape[-1] == d)

    assert width(None) == mp * page           # capacity-wide gather
    assert width(2) == 128                    # 16 keys, 128-clamped block
