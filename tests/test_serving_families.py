"""The serving engine against the dense-cache oracle, one model of each
family: dense with window layers, SSM, hybrid, multi-codebook audio, MoE,
and the benchmarked dense configuration.

Each case serves three prompts of mixed lengths through the paged engine
with chunked prefill (``CHUNK`` positions a chunk, so two of the three
prompts split) and requires every greedy token to equal the oracle's.
Both sides run the xla backend: exact equality is an XLA-vs-XLA contract
(the Pallas kernels' online-softmax accumulation is tolerance-close, not
bit-identical, in bf16). The comparison therefore also holds that
splitting a prompt across chunk calls -- self-attention for chunk 0, the
block-table gather for continuations, resumed conv/SSM state for the
recurrent families -- reproduces the single-pass token stream.
"""

import numpy as np
import pytest

from _oracle import reference_tokens
from repro import configs
from repro.serving import ServingEngine

PROMPT_LENS = (11, 16, 7)          # mixed lengths: distinct page counts
GEN_LENS = (6, 3, 5)               # mixed depths: slots recycle mid-run
CHUNK = 8                          # < the longer prompts: multi-chunk paths


@pytest.mark.parametrize("arch", ["gemma2-2b", "mamba2-1.3b", "hymba-1.5b",
                                  "musicgen-medium", "granite-moe-3b-a800m",
                                  "qwen1.5-4b"])
def test_engine_matches_oracle(arch):
    model_cfg = configs.get_smoke(arch)
    rng = np.random.default_rng(0)
    engine = ServingEngine(model_cfg, max_slots=2, max_context=64,
                           page_size=16, n_pages=24, temperature=0.0,
                           seed=0, backend="xla", prefill_chunk=CHUNK)
    prompts = []
    for plen, glen in zip(PROMPT_LENS, GEN_LENS):
        shape = ((plen, model_cfg.n_codebooks)
                 if model_cfg.n_codebooks > 1 else (plen,))
        prompt = rng.integers(0, model_cfg.vocab, shape).astype(np.int32)
        prompts.append(prompt)
        engine.submit(prompt, glen)
    report = engine.run()
    assert report["summary"]["prefill_chunks"] > len(PROMPT_LENS)
    for r, prompt, glen in zip(report["requests"], prompts, GEN_LENS):
        want = reference_tokens(model_cfg, engine.params, prompt, glen)
        np.testing.assert_array_equal(np.asarray(r["tokens"], np.int32),
                                      want, err_msg=f"rid {r['rid']}")
