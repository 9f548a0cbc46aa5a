"""The dense-cache oracle loop that the serving engine is checked against.

One request at a time through ``transformer.prefill_into_cache`` and
``transformer.decode_step`` on a contiguous KV/SSM cache, greedy. Shared
by tests/test_serving.py and tests/test_serving_families.py.
"""

import jax.numpy as jnp
import numpy as np

from repro.core.config import GemminiConfig
from repro.core.generator import elaborate
from repro.models import transformer as tf


def reference_tokens(model_cfg, params, prompt, gen):
    """Greedy tokens for ``prompt``: (gen,), or (gen, n_q) for a
    multi-codebook model."""
    engine = elaborate(GemminiConfig(input_dtype="bf16", acc_dtype="fp32",
                                     output_dtype="bf16"), "xla")
    t = len(prompt) + model_cfg.n_meta_tokens
    st = tf.init_decode_state(model_cfg, 1, t + gen, dtype=model_cfg.dtype)
    st = st._replace(pos=jnp.zeros((), jnp.int32))
    logits, st = tf.prefill_into_cache(engine, params, model_cfg,
                                       jnp.asarray(prompt[None]), st)
    toks, last = [], logits[0, t - 1]
    for _ in range(gen):
        nxt = np.asarray(jnp.argmax(last, axis=-1), np.int32)
        toks.append(nxt)
        step = nxt.reshape(1, 1) if nxt.ndim == 0 else nxt.reshape(1, 1, -1)
        logits, st = tf.decode_step(engine, params, model_cfg,
                                    jnp.asarray(step), st)
        last = logits[0, -1]
    return np.stack(toks)
