"""Toy-sized cells for the CPU tests: a cell of the benchmark's qwen1.5-4b
configuration with every size cut down, serving one of the mix files, the
program's model built at those sizes, the ``xla`` backend, and the v5e
peaks under the CPU's name."""

from __future__ import annotations

import copy
import json

from bench import harness, traffic

QWEN = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=512)


def toy_cell(mix: str, *, limit: float = 0.25, min_tokens: int = 50):
    """A qwen1.5-4b cell serving the mix file ``mix``, at toy size, and its
    toy program model."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(next(w["name"] for w in bench["workloads"]
                                  if w["config"] == "qwen1.5-4b"))
    cfg = copy.deepcopy(cell.config)
    cfg["config"].update(QWEN)
    cfg["engine"] = dict(cfg["engine"], backend="xla", max_slots=4,
                         max_context=448, n_pages=28)
    cfg["check"] = dict(cfg["check"], sample_requests=8,
                        max_logit_gap=limit, min_tokens=min_tokens)
    cell.config = cfg
    cell.mix = traffic.load(mix)
    for k in ("prompt", "output"):
        cell.mix[k] = dict(cell.mix[k], max=min(cell.mix[k]["max"], 192),
                           median=min(cell.mix[k]["median"], 96))
    if cell.mix["kind"] == "closed":
        cell.mix["clients"] = 4
    else:
        cell.mix["rate_rps"] = 8.0     # every slot busy
    return cell, harness.model_config(cell)


def patch(monkeypatch, mc):
    monkeypatch.setattr(harness, "model_config", lambda cell: mc)
    monkeypatch.setattr(harness, "load_peaks",
                        lambda kind: {"bf16_flops": 197e12,
                                      "hbm_bytes_s": 819e9})
