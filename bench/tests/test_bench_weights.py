"""The benchmark's own weights fit the program's parameter layout, and its
plain float32 references agree with the program at toy sizes."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.tests import toy


def test_weights_match_program_layout():
    from repro.models import transformer as tf
    cell, mc = toy.toy_cell("chat")
    ours = jax.eval_shape(lambda k: cell.model.init_params(k, cell.config),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: tf.init_params(k, mc),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_reference_agrees_with_program_forward():
    """The reference's float32 logits against the program's own forward
    pass (xla backend, bf16 weights) on the same weights: they differ by
    bf16 rounding only."""
    from repro.core.context import ExecutionContext
    from repro.models import transformer as tf
    cell, mc = toy.toy_cell("chat")
    params = harness.make_params(cell, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, mc.vocab, (2, 48)), jnp.int32)
    ref = cell.model.hidden(params, tokens, cell.config, False)
    ref = cell.model.head(params, cell.config)(ref, False)
    got = tf.forward(ExecutionContext(cfg=None, backend="xla"), params, mc,
                     tokens).reshape(ref.shape)
    err = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert err < 2e-2, err
    # a wrong layout (another seed's weights) is nowhere near
    other = harness.make_params(cell, 4)
    bad = tf.forward(ExecutionContext(cfg=None, backend="xla"), other, mc,
                     tokens).reshape(ref.shape)
    assert float(jnp.linalg.norm(bad - ref) / jnp.linalg.norm(ref)) > 0.5
