# Known-bad kernel source, AST-scanned by the lint golden tests
# (tests/test_lint.py). NEVER imported or executed — each function below
# exists to trip exactly one source-level diagnostic, locking the rule's
# behavior. Do not "fix" these.

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.contracts import kernel_contract


def _body(a_ref, o_ref):
    # GL502: dot_general with no preferred_element_type — bf16 inputs
    # would accumulate at input precision.
    o_ref[...] = jax.lax.dot_general(
        a_ref[...], a_ref[...], (((1,), (0,)), ((), ())))


def unannotated_launch(a):
    # GL501: pallas_call in a function with no @kernel_contract.
    # GL503: no compiler_params -> Mosaic serializes every axis.
    return pl.pallas_call(
        _body,
        grid=(4,),
        in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
    )(a)


@kernel_contract("no_such_contract")
def unregistered_launch(a):
    # GL501 (unregistered): the annotation names no registered builder.
    # GL504: input_output_aliases undeclared by any contract.
    # GL505: rank-1 scalar BlockSpec without memory_space.
    return pl.pallas_call(
        _body,
        grid=(4,),
        in_specs=[pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(a)


def resurrected_shim(op):
    # GL506: the removed ops.*(backend=...) deprecation machinery.
    return _deprecated_shim(op)  # noqa: F821 — deliberately undefined
