"""Generated GEMM engine: interpret=True kernel vs pure-jnp oracle.

Sweeps shapes, dtypes, dataflows, bias, shift, activation -- bit-exact for
the integer datapath, allclose for float paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # container has no hypothesis
    from _hyp import given, settings, strategies as st

from repro.core.config import Activation, Dataflow, GemminiConfig
from repro.core.generator import elaborate
from repro.core.context import ExecutionContext
from repro.core.tiling import plan_gemm
from repro.kernels import contracts, ref
from repro.models import layers


def _ctx(cfg, backend="interpret"):
    return ExecutionContext(cfg=cfg, backend=backend)


def _ints(rng, shape, lo=-128, hi=128, dtype=jnp.int8):
    return jnp.asarray(rng.integers(lo, hi, shape), dtype)


def _floats(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("df", [Dataflow.OS, Dataflow.WS])
@pytest.mark.parametrize("shape", [(128, 128, 128), (200, 136, 260),
                                   (1, 1000, 784), (384, 128, 512)])
@pytest.mark.parametrize("bias", [False, True])
def test_int8_gemm_bitexact(rng, df, shape, bias):
    m, n, k = shape
    cfg = GemminiConfig(dataflow=df)
    a = _ints(rng, (m, k))
    b = _ints(rng, (k, n))
    d = _ints(rng, (1, n), -1000, 1000, jnp.int32) if bias else None
    y = _ctx(cfg).gemm(a, b, d, shift=8, activation=Activation.RELU)
    yr = ref.gemm_ref(a, b, d, acc_dtype=jnp.int32, out_dtype=jnp.int8,
                      shift=8, activation=Activation.RELU)
    assert y.dtype == jnp.int8
    assert bool(jnp.all(y == yr))


@pytest.mark.parametrize("df", [Dataflow.OS, Dataflow.WS])
@pytest.mark.parametrize("in_dt,acc_dt,out_dt",
                         [("bf16", "fp32", "bf16"), ("fp32", "fp32", "fp32")])
def test_float_gemm_allclose(rng, df, in_dt, acc_dt, out_dt):
    cfg = GemminiConfig(dataflow=df, input_dtype=in_dt, acc_dtype=acc_dt,
                        output_dtype=out_dt)
    a = _floats(rng, (160, 96)).astype(cfg.input_jnp)
    b = _floats(rng, (96, 224)).astype(cfg.input_jnp)
    y = _ctx(cfg).gemm(a, b, None)
    yr = ref.gemm_ref(a, b, None, acc_dtype=cfg.acc_jnp,
                      out_dtype=cfg.output_jnp)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=2e-2 if in_dt == "bf16" else 1e-5,
                               atol=1e-2 if in_dt == "bf16" else 1e-5)


@settings(max_examples=12, deadline=None)
@given(m=st.integers(1, 300), n=st.integers(1, 300), k=st.integers(1, 300),
       df=st.sampled_from([Dataflow.OS, Dataflow.WS]),
       shift=st.integers(0, 12))
def test_int8_gemm_property(m, n, k, df, shift):
    rng = np.random.default_rng(m * 7 + n * 3 + k)
    cfg = GemminiConfig(dataflow=df)
    a = _ints(rng, (m, k))
    b = _ints(rng, (k, n))
    y = _ctx(cfg).gemm(a, b, None, shift=shift)
    yr = ref.gemm_ref(a, b, None, acc_dtype=jnp.int32, out_dtype=jnp.int8,
                      shift=shift)
    assert bool(jnp.all(y == yr))


def test_os_ws_agree(rng):
    """Both dataflows compute the same function (different schedules)."""
    cfg = GemminiConfig(dataflow=Dataflow.BOTH)
    a = _ints(rng, (256, 192))
    b = _ints(rng, (192, 320))
    d = _ints(rng, (1, 320), -500, 500, jnp.int32)
    y_os = _ctx(cfg).gemm(a, b, d, dataflow=Dataflow.OS, shift=7,
                          activation=Activation.RELU6)
    y_ws = _ctx(cfg).gemm(a, b, d, dataflow=Dataflow.WS, shift=7,
                          activation=Activation.RELU6)
    assert bool(jnp.all(y_os == y_ws))


def test_pipeline_depth_1_same_numerics(rng):
    """Design point 6 ("fully combinational"): schedule changes, math not."""
    a = _ints(rng, (256, 128))
    b = _ints(rng, (128, 128))
    y2 = _ctx(GemminiConfig(pipeline_depth=2)).gemm(a, b, None, shift=4)
    y1 = _ctx(GemminiConfig(pipeline_depth=1)).gemm(a, b, None, shift=4)
    assert bool(jnp.all(y1 == y2))


def test_xla_backend_matches_interpret(rng):
    """The dry-run path and the kernel path share numerics."""
    cfg = GemminiConfig()
    a = _ints(rng, (130, 70))
    b = _ints(rng, (70, 36))
    yi = _ctx(cfg).gemm(a, b, None, shift=6, activation=Activation.RELU)
    yx = _ctx(cfg, "xla").gemm(a, b, None, shift=6, activation=Activation.RELU)
    assert bool(jnp.all(yi == yx))


def test_engine_header_is_consistent():
    eng = elaborate(GemminiConfig(), "interpret")
    h = eng.header(1000, 512, 2048)
    assert h["TILE_M"] % h["DIM"] == 0
    assert h["GRID"][0] * h["TILE_M"] >= 1000
    assert 0 < h["UTILIZATION"] <= 1.0


def test_matmul_batched_lhs(rng):
    cfg = GemminiConfig(input_dtype="fp32", acc_dtype="fp32",
                        output_dtype="fp32")
    eng = elaborate(cfg, "interpret")
    a = _floats(rng, (2, 3, 40))
    b = _floats(rng, (40, 24))
    y = eng.matmul(a, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(a) @ np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# stacked weights: one layer of an (S, K, N) stack, read in place
# ---------------------------------------------------------------------------
_STACK = 3
# bf16 serving datapath with 128-wide tiles: (8, 256, 384) runs a 1x3x2
# grid on an unpadded plan; (8, 200, 136) pads K and N
_BF16 = dict(input_dtype="bf16", acc_dtype="fp32", output_dtype="bf16",
             max_tile_m=128, max_tile_n=128, max_tile_k=128)


def _pallas_calls(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _pallas_calls(sub)


@pytest.mark.parametrize("df", [Dataflow.OS, Dataflow.WS])
@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("layer", [0, _STACK - 1])
@pytest.mark.parametrize("shape", [(8, 384, 256), (8, 136, 200)])
def test_stacked_gemm_equals_layer_slice(rng, df, bias, layer, shape):
    """The GEMM on layer ``layer`` of a stack equals the GEMM on
    ``stack[layer]`` bit for bit. Where the plan pads neither K nor N the
    kernel takes the whole stack and the layer index; where it pads, the
    layer is sliced out, padded, and enters as a stack of one."""
    m, n, k = shape
    cfg = GemminiConfig(dataflow=df, **_BF16)
    ctx = _ctx(cfg)
    a = _floats(rng, (m, k), jnp.bfloat16)
    stack = _floats(rng, (_STACK, k, n), jnp.bfloat16)
    d = _floats(rng, (1, n)) if bias else None

    def stacked(a, stack, d, li):
        return ctx.gemm(a, stack, d, layer=li)

    li = jnp.int32(layer)
    y = jax.jit(stacked)(a, stack, d, li)
    assert bool(jnp.all(y == ctx.gemm(a, stack[layer], d)))

    plan = plan_gemm(cfg, m, n, k, has_bias=bias)
    (call,) = _pallas_calls(jax.make_jaxpr(stacked)(a, stack, d, li).jaxpr)
    shapes = [x.shape for x in call.params["grid_mapping"].in_shapes]
    assert shapes[0] == (1,)                      # the prefetched layer
    in_place = (plan.k, plan.n) == (k, n)
    assert in_place == (shape == (8, 384, 256))
    weight = (_STACK * k, n) if in_place else (plan.k, plan.n)
    assert shapes[1 if df is Dataflow.WS else 2] == weight


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_project_reads_stacked_weight(rng, backend):
    """``layers.project`` on a :class:`layers.StackedWeight` equals the
    projection on the sliced layer, on the float path and the engine."""
    ctx = _ctx(GemminiConfig(**_BF16), backend)
    x = _floats(rng, (2, 4, 256), jnp.bfloat16)
    stack = _floats(rng, (_STACK, 256, 384), jnp.bfloat16)
    bias = _floats(rng, (384,), jnp.bfloat16)
    y = layers.project(ctx, x, layers.StackedWeight(stack, jnp.int32(1)),
                       bias)
    assert y.shape == (2, 4, 384)
    assert bool(jnp.all(y == layers.project(ctx, x, stack[1], bias)))


def test_stacked_weight_needs_its_layer(rng):
    ctx = _ctx(GemminiConfig(**_BF16))
    a = _floats(rng, (8, 256), jnp.bfloat16)
    stack = _floats(rng, (_STACK, 256, 384), jnp.bfloat16)
    with pytest.raises(ValueError, match="layer"):
        ctx.gemm(a, stack, None)
    with pytest.raises(ValueError, match="layer"):
        ctx.gemm(a, stack[0], None, layer=0)


@pytest.mark.parametrize("df", [Dataflow.OS, Dataflow.WS])
def test_gemm_contract_matches_kernel(df):
    """The contract declares the grid, blocks, stacked weight and
    prefetched layer the kernel launches with."""
    from repro.kernels import gemm as gemm_kernel
    cfg = GemminiConfig(dataflow=df, **_BF16)
    plan = plan_gemm(cfg, 8, 384, 256)
    build = (contracts.gemm_os_contract if df is Dataflow.OS
             else contracts.gemm_ws_contract)
    c = build(cfg, plan, n_layers=_STACK)
    launch = gemm_kernel.gemm_os if df is Dataflow.OS else gemm_kernel.gemm_ws
    jaxpr = jax.make_jaxpr(lambda a, b, li: launch(
        a, b, None, plan, cfg, layer=li, interpret=True))(
        jnp.zeros((plan.m, plan.k), jnp.bfloat16),
        jnp.zeros((_STACK, plan.k, plan.n), jnp.bfloat16), jnp.int32(0))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    assert gm.num_index_operands == c.scalar_prefetch == 1
    assert tuple(gm.grid) == tuple(n for _, n in c.grid)
    ops = c.inputs + c.outputs
    assert [tuple(x.shape) for x in list(gm.in_shapes)[1:]] == \
        [op.shape for op in c.inputs]
    blocks = [tuple(getattr(x, "block_size", x) for x in bm.block_shape)
              for bm in gm.block_mappings]
    assert blocks == [op.block for op in ops]
