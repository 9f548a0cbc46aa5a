"""The main path's Pallas kernels compile for a TPU v5e (Mosaic), at the
widths of the models they serve: qwen1.5-4b for the GEMM and attention
kernels, mamba2-1.3b for the fused SSD kernel.

The chip is described, not attached (``jax.experimental.topologies``):
nothing runs, but Mosaic refuses here what it would refuse on the chip --
block shapes off the tiling, scalar blocks it cannot place, primitives it
cannot lower, more fast memory than a kernel may use. Interpret-mode tests
see none of that.

The topology is described inside a module fixture: only the process that
runs these tests loads the TPU compiler, and every pytest worker collects
the same tests.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import flags
from repro.core.config import GemminiConfig
from repro.kernels import ops

# qwen1.5-4b: d=2560, 20 heads x 128 (MHA), d_ff=6912, QKV width 3 x 2560
D, HEADS, HEAD_DIM, D_FF = 2560, 20, 128, 6912
# the serving engine's geometry for 4 slots of 608 positions at page 64
SLOTS, PAGE, MAX_PAGES, N_PAGES = 4, 64, 10, 40
# mamba2-1.3b: 64 heads x 64, d_state 128, one group, chunk 256
SSM_HEADS, SSM_HEAD_DIM, D_STATE, SSM_CHUNK = 64, 64, 128, 256

_ENGINE = GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                        output_dtype="bf16")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    prev_tune = flags.get("tune_mode")
    flags.set_flag("tune_mode", "off")
    yield
    flags.set_flag("tune_mode", prev_tune)
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _gemm(m, k, n, bias):
    def f(a, b, d=None):
        return ops.gemm_impl(a, b, d, cfg=_ENGINE, backend="pallas")
    shapes = [((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16)]
    if bias:
        shapes.append(((n,), jnp.bfloat16))
    return f, shapes


def _flash():
    def f(q, k, v):
        return ops.flash_attention_impl(q, k, v, backend="pallas")
    return f, [((1, 512, HEADS, HEAD_DIM), jnp.bfloat16)] * 3


def _pool():
    """Two layers' pools, stacked as the paged steps carry them."""
    return ((2, HEADS, N_PAGES + 1, PAGE, HEAD_DIM), jnp.bfloat16)


def _paged_decode():
    def f(q, kp, vp, bt, ln, layer):
        return ops.paged_attention_impl(q, kp, vp, bt, ln, layer,
                                        backend="pallas")
    return f, [((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16), _pool(), _pool(),
               ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32),
               ((), jnp.int32)]


def _paged_prefill_chunk():
    def f(q, kp, vp, bt, start, layer):
        return ops.paged_prefill_attention_impl(
            q, kp, vp, bt, start, layer, kv_pages=8, backend="pallas")
    return f, [((1, PAGE, HEADS, HEAD_DIM), jnp.bfloat16), _pool(), _pool(),
               ((MAX_PAGES,), jnp.int32), ((), jnp.int32), ((), jnp.int32)]


def _ssd():
    def f(x, dt, a_log, b, c):
        return ops.ssd_impl(x, dt, a_log, b, c, chunk=SSM_CHUNK,
                            return_final_state=True, backend="pallas")
    t = 512
    return f, [((1, t, SSM_HEADS, SSM_HEAD_DIM), jnp.bfloat16),
               ((1, t, SSM_HEADS), jnp.float32), ((SSM_HEADS,), jnp.float32),
               ((1, t, 1, D_STATE), jnp.bfloat16),
               ((1, t, 1, D_STATE), jnp.bfloat16)]


CASES = {
    "gemm_qkv_bias": lambda: _gemm(512, D, 3 * D, True),
    "gemm_up": lambda: _gemm(512, D, D_FF, False),
    "gemm_down": lambda: _gemm(512, D_FF, D, False),
    "gemm_decode_qkv_bias": lambda: _gemm(SLOTS, D, 3 * D, True),
    "flash_attention": _flash,
    "paged_decode_attention": _paged_decode,
    "paged_prefill_attention": _paged_prefill_chunk,
    "ssd_fused": _ssd,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_paged_decode_contract_matches_kernel():
    """At the decode case's shape one grid step takes a page of every KV
    head: the contract's grid is (slot, page), B x MP steps with no head
    axis, and it is the grid and blocks the kernel launches with."""
    from repro.kernels import contracts as kc

    c = kc.paged_decode_attention_contract(
        _ENGINE, b=SLOTS, h=HEADS, kvh=HEADS, d=HEAD_DIM, page=PAGE,
        mp=MAX_PAGES, n_pages=N_PAGES + 1, n_layers=2)
    assert [n for n, _ in c.grid] == ["bb", "j"]
    assert math.prod(n for _, n in c.grid) == SLOTS * MAX_PAGES

    _, shapes = _paged_decode()
    jaxpr = jax.make_jaxpr(lambda *a: ops.paged_attention_impl(
        *a, cfg=_ENGINE, backend="interpret"))(
        *[jax.ShapeDtypeStruct(sh, dt) for sh, dt in shapes])
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    assert tuple(gm.grid) == tuple(n for _, n in c.grid)
    blocks = [tuple(getattr(x, "block_size", x) for x in bm.block_shape)
              for bm in gm.block_mappings]
    assert blocks == [op.block for op in c.inputs + c.outputs]


# -- the paged steps keep the KV arena in place -----------------------------
# qwen1.5-4b at two layers, 8 slots of 1088 positions at page 64; the
# arena's size is the only thing that differs between the two compiles
STEP_LAYERS, STEP_SLOTS, STEP_MAX_PAGES, STEP_KV_PAGES = 2, 8, 17, 6


def _step_program(step, n_pages, one_chip):
    """``step``'s jitted program, as the serving engine builds it (the
    state donated), compiled for one described v5e chip."""
    import dataclasses

    from repro import configs
    from repro.core.context import ExecutionContext
    from repro.models import transformer as tf

    mc = dataclasses.replace(configs.get("qwen1.5-4b"),
                             n_layers=STEP_LAYERS)
    ctx = ExecutionContext(cfg=_ENGINE, backend="pallas")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), mc)))
    state = jax.tree.map(spec, jax.eval_shape(
        lambda: tf.init_paged_state(mc, STEP_SLOTS, n_pages, PAGE,
                                    STEP_MAX_PAGES)))
    i32 = jnp.int32
    if step == "decode":
        fn = jax.jit(lambda p, tok, st, act: tf.paged_decode_step(
            ctx, p, mc, tok, st, act, page_size=PAGE), donate_argnums=(2,))
        args = (params, spec(jax.ShapeDtypeStruct((STEP_SLOTS, 1), i32)),
                state, spec(jax.ShapeDtypeStruct((STEP_SLOTS,), jnp.bool_)))
    else:
        fn = jax.jit(lambda p, tok, st, slot, pages, start:
                     tf.paged_prefill_chunk(
                         ctx, p, mc, tok, st, slot, pages, start,
                         page_size=PAGE, kv_pages=STEP_KV_PAGES),
                     donate_argnums=(2,))
        args = (params, spec(jax.ShapeDtypeStruct((1, PAGE), i32)), state,
                spec(jax.ShapeDtypeStruct((), i32)),
                spec(jax.ShapeDtypeStruct((STEP_MAX_PAGES,), i32)),
                spec(jax.ShapeDtypeStruct((), i32)))
    return fn.lower(*args).compile()


@pytest.mark.parametrize("step", ["chunk", "decode"])
def test_paged_step_keeps_arena_in_place(step, one_chip):
    """The paged steps write and read the stacked KV pools where they lie:
    the step's scratch does not grow with the arena, and no instruction
    copies or allocates a stacked pool or materializes one layer's pool."""
    small = _step_program(step, 56, one_chip)
    big = _step_program(step, 112, one_chip)
    grown = (big.memory_analysis().temp_size_in_bytes
             - small.memory_analysis().temp_size_in_bytes)
    assert abs(grown) < 4e6, f"scratch grows with the arena by {grown} B"

    stack = f"bf16[{STEP_LAYERS},{HEADS},113,{PAGE},{HEAD_DIM}]"
    layer = f"bf16[{HEADS},113,{PAGE},{HEAD_DIM}]"
    offenders = []
    for line in big.as_text().splitlines():
        _, eq, rhs = line.partition(" = ")
        if not eq:
            continue
        if rhs.startswith(layer + "{") or (
                rhs.startswith(stack + "{") and (
                    " copy(" in rhs or '"AllocateBuffer"' in rhs)):
            offenders.append(line.strip()[:160])
    assert not offenders, "\n".join(offenders)
