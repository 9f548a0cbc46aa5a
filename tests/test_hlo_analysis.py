"""Loop-aware HLO analyzer: the roofline's source of truth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo, parse_module
from repro.analysis import roofline


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_scan_flops_scaled_by_trip_count():
    def scan10(x, w):
        def f(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(f, x, None, length=10)
        return y

    def unrolled10(x, w):
        for _ in range(10):
            x = jnp.tanh(x @ w)
        return x

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    cs = _compile(scan10, x, w)
    cu = _compile(unrolled10, x, w)
    rs, ru = analyze_hlo(cs.as_text()), analyze_hlo(cu.as_text())
    analytic_dots = 10 * 2 * 256 ** 3

    # XLA's builtin undercounts the scan ~10x -- the bug we fix:
    ca = cs.cost_analysis()
    assert ca["flops"] < 0.2 * analytic_dots
    # our analyzer agrees with both the unrolled version and the math:
    assert abs(rs.flops - ru.flops) / ru.flops < 0.01
    assert abs(rs.flops - analytic_dots) / analytic_dots < 0.01
    assert rs.n_while == 1 and rs.max_trip == 10


def test_nested_scan_multipliers():
    def nested(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    r = analyze_hlo(_compile(nested, x, w).as_text())
    analytic = 3 * 4 * 2 * 128 ** 3
    assert abs(r.flops - analytic) / analytic < 0.02


def test_dot_general_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    r = analyze_hlo(_compile(f, a, b).as_text())
    analytic = 2 * 4 * 64 * 32 * 16
    assert abs(r.flops - analytic) / analytic < 0.01


def test_bytes_sane():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    r = analyze_hlo(_compile(f, a, b).as_text())
    io_bytes = 3 * 512 * 512 * 4
    assert io_bytes <= r.bytes <= 2 * io_bytes


def test_collectives_multiplied(run_subprocess):
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.analysis.hlo import analyze_hlo
from repro.launch.mesh import activate_mesh, make_mesh

mesh = make_mesh((8,), ("model",))
def f(x, w):
    def body(c, _):
        y = jax.lax.with_sharding_constraint(
            c @ w, NamedSharding(mesh, P(None, "model")))
        y = jax.lax.with_sharding_constraint(
            y @ w.T, NamedSharding(mesh, P()))
        return y, None
    y, _ = jax.lax.scan(body, x, None, length=5)
    return y

x = jax.ShapeDtypeStruct((128, 1024), jnp.float32,
                         sharding=NamedSharding(mesh, P()))
w = jax.ShapeDtypeStruct((1024, 1024), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, "model")))
with activate_mesh(mesh):
    c = jax.jit(f).lower(x, w).compile()
r = analyze_hlo(c.as_text())
per_step = 128 * 1024 * 4
total = sum(v for v in r.coll_breakdown.values())
assert abs(total - 5 * per_step) / (5 * per_step) < 0.05, r.coll_breakdown
print("COLL OK", r.coll_breakdown)
"""
    out = run_subprocess(code, n_devices=8)
    assert "COLL OK" in out


def test_parse_module_structure():
    def f(x):
        return jnp.sum(x * 2)
    txt = _compile(f, jax.ShapeDtypeStruct((64,), jnp.float32)).as_text()
    comps, entry = parse_module(txt)
    assert entry and entry in comps
    assert any(op.opcode in ("multiply", "fusion", "reduce")
               for op in comps[entry].ops) or len(comps) > 1


def test_roofline_fraction_math():
    v5e = roofline.peaks("TPU v5 lite")
    rl = roofline.Roofline(
        arch="x", shape="train_4k", mesh="16x16",
        flops=1e12, hbm_bytes=1e11, coll_bytes=1e9,
        coll_breakdown={}, per_device_hbm_peak=1e10,
        model_flops=2.56e14, n_chips=256)
    # terms
    assert abs(rl.t_compute - 1e12 / v5e.flops_bf16) < 1e-12
    assert abs(rl.t_memory - 1e11 / v5e.hbm_bw) < 1e-12
    assert rl.bottleneck == "memory"
    ideal = 2.56e14 / 256 / v5e.flops_bf16
    assert abs(rl.roofline_fraction - ideal / rl.t_bound) < 1e-9


def test_peaks_table_is_keyed_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.ops_int8, v5e.hbm_bw) == (197e12, 393e12,
                                                          819e9)
    # a device without published peaks is an error, not a default
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")


# ---------------------------------------------------------------------------
# parser edge cases (synthetic HLO text: deterministic and independent of
# what this compiler version happens to emit)
# ---------------------------------------------------------------------------
_WHILE_HLO = """
HloModule synthetic_while

%body (p: (s32[], f32[128,128])) -> (s32[], f32[128,128]) {
  %p = (s32[], f32[128,128]) parameter(0)
  %iv = s32[] get-tuple-element((s32[], f32[128,128]) %p), index=0
  %one = s32[] constant(1)
  %ivn = s32[] add(s32[] %iv, s32[] %one)
  %x = f32[128,128] get-tuple-element((s32[], f32[128,128]) %p), index=1
  %y = f32[128,128] dot(f32[128,128] %x, f32[128,128] %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[128,128]) tuple(s32[] %ivn, f32[128,128] %y)
}

%cond (q: (s32[], f32[128,128])) -> pred[] {
  %q = (s32[], f32[128,128]) parameter(0)
  %qiv = s32[] get-tuple-element((s32[], f32[128,128]) %q), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(s32[] %qiv, s32[] %n), direction=LT
}

ENTRY %main (arg: f32[128,128]) -> f32[128,128] {
  %arg = f32[128,128] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[128,128]) tuple(s32[] %zero, f32[128,128] %arg)
  %w = (s32[], f32[128,128]) while((s32[], f32[128,128]) %init), condition=%cond, body=%body{ANNOT}
  ROOT %out = f32[128,128] get-tuple-element((s32[], f32[128,128]) %w), index=1
}
"""


def test_trip_count_condition_fallback():
    # no backend_config: the condition's compare-against-constant(7) is
    # the only trip-count evidence
    r = analyze_hlo(_WHILE_HLO.replace("{ANNOT}", ""))
    assert r.n_while == 1 and r.max_trip == 7
    # dot + the s32 add (body) + the compare (cond), each executed x7
    assert r.flops == 7 * (2 * 128 ** 3 + 1 + 1)


def test_trip_count_known_annotation_wins():
    annot = (', backend_config={"known_trip_count":{"n":"12"}}')
    r = analyze_hlo(_WHILE_HLO.replace("{ANNOT}", annot))
    assert r.max_trip == 12                 # annotation beats the fallback 7
    assert r.flops == 12 * (2 * 128 ** 3 + 1 + 1)


_ZERO_HLO = """
HloModule synthetic_zero

ENTRY %main (a: f32[0,128], b: f32[128,64]) -> f32[0,64] {
  %a = f32[0,128] parameter(0)
  %b = f32[128,64] parameter(1)
  %d = f32[0,64] dot(f32[0,128] %a, f32[128,64] %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %r = f32[0,64] tanh(f32[0,64] %d)
}
"""


def test_zero_sized_operands():
    # a zero-element operand (empty expert / degenerate shard) must not
    # crash or contribute flops; only the nonzero operand costs bytes
    r = analyze_hlo(_ZERO_HLO)
    assert r.flops == 0.0
    assert r.bytes == 128 * 64 * 4          # %b read by the dot; rest is 0


_NESTED_FUSION_HLO = """
HloModule synthetic_nested_fusion

%inner (p0: f32[128]) -> f32[128] {
  %p0 = f32[128] parameter(0)
  ROOT %t = f32[128] tanh(f32[128] %p0)
}

%outer (q0: f32[128]) -> f32[128] {
  %q0 = f32[128] parameter(0)
  %m = f32[128] multiply(f32[128] %q0, f32[128] %q0)
  ROOT %f = f32[128] fusion(f32[128] %m), kind=kLoop, calls=%inner
}

ENTRY %main (a: f32[128]) -> f32[128] {
  %a = f32[128] parameter(0)
  ROOT %g = f32[128] fusion(f32[128] %a), kind=kLoop, calls=%outer
}
"""


def test_nested_fusion_flops_once_bytes_at_boundary():
    # ops inside (transitively) fused bodies cost flops exactly once, and
    # HBM bytes accrue only at the outermost fusion's operands/results
    r = analyze_hlo(_NESTED_FUSION_HLO)
    assert r.flops == 256.0                 # multiply(128) + tanh(128)
    assert r.bytes == 2 * 128 * 4           # %a in, %g out -- nothing inner


def test_no_entry_raises():
    with pytest.raises(ValueError, match="ENTRY"):
        analyze_hlo("%orphan (p: f32[4]) -> f32[4] {\n"
                    "  ROOT %p = f32[4] parameter(0)\n}\n")
