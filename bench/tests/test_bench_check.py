"""The output check decides ``correct``: a sound run passes, a run whose
timed path is broken underneath fails, and the control -- the reference in
float8 in the program's place -- lies above the limit. Toy sizes on the
CPU; the chip's check is the same code at the cells' sizes."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, traffic
from bench.tests import toy
from repro.serving.engine import ServingEngine

SEED = 2 ** 31 + 1234


def _token_altered(monkeypatch):
    """The token of the first slot of every decode step is changed where
    it is produced."""
    orig = ServingEngine._sample

    def sample(self, logits):
        out = orig(self, logits)
        if out.ndim == 1 and out.shape[0] > 1:
            out = out.copy()
            out[0] = (out[0] + 1) % self.model_cfg.vocab
        return out

    monkeypatch.setattr(ServingEngine, "_sample", sample)


def _state_unchanged(monkeypatch):
    """The decode step returns the state it was given."""
    orig = ServingEngine._dispatch

    def dispatch(self, which, args):
        if which != "decode":
            return orig(self, which, args)
        keep = args[2]
        logits, _ = orig(self, which, args[:2]
                         + (jax.tree.map(jnp.copy, keep),) + args[3:])
        return logits, keep

    monkeypatch.setattr(ServingEngine, "_dispatch", dispatch)


def _half_batch(monkeypatch):
    """The decode step leaves out half of its slots (every odd one): their
    rows are computed as padding, and their tokens still served."""
    orig = ServingEngine._dispatch

    def dispatch(self, which, args):
        if which == "decode":
            act = args[3]
            args = args[:3] + (act & (jnp.arange(act.shape[0]) % 2 == 0),)
        return orig(self, which, args)

    monkeypatch.setattr(ServingEngine, "_dispatch", dispatch)


FAULTS = {"token_altered": _token_altered,
          "state_unchanged": _state_unchanged,
          "half_batch": _half_batch}


def _run(monkeypatch, mix, fault=None):
    cell, mc = toy.toy_cell(mix)
    toy.patch(monkeypatch, mc)
    if fault:
        FAULTS[fault](monkeypatch)
    return cell, harness.run(cell, SEED, 3.0, False,
                             t_start=time.monotonic())


MIXES = ["chat", "decode"]


@pytest.mark.parametrize("mix", MIXES)
def test_sound_run_is_correct(monkeypatch, mix):
    cell, out = _run(monkeypatch, mix)
    chk = out["check"]
    assert out["correct"], chk
    assert chk["tokens_compared"]["value"] >= 50
    assert chk["max_logit_gap"]["value"] < 0.1
    assert set(out["metrics"]) == {m["name"] for m in cell.metrics}
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("mix", MIXES)
def test_broken_timed_path_is_not_correct(monkeypatch, mix, fault):
    _, out = _run(monkeypatch, mix, fault)
    assert not out["correct"]
    assert out["check"]["max_logit_gap"]["value"] > 1.0


@pytest.mark.parametrize("mix", MIXES)
def test_control_lies_above_the_limit(monkeypatch, mix):
    """Over the same served requests, the check a run makes passes the
    program and fails the fp8 control, whose widest gap lies above the
    limit."""
    cell, mc = toy.toy_cell(mix)
    toy.patch(monkeypatch, mc)
    params = harness.make_params(cell, SEED)
    engine = harness.make_engine(cell, mc, params)
    items = traffic.generate(cell.mix, SEED, 3.0, mc.vocab)
    served, _, _ = harness.serve(cell, engine, items, 3.0,
                                 harness.Hooks(engine, 3.0, None))
    ok, prog = harness.check_outputs(cell, params, served, SEED)
    bad, ctrl = harness.check_outputs(cell, params, served, SEED,
                                      control=True)
    limit = cell.config["check"]["max_logit_gap"]
    assert ok and not bad, (prog, ctrl)
    assert prog["tokens_compared"]["value"] >= 50
    assert ctrl["tokens_compared"] == prog["tokens_compared"]
    assert prog["max_logit_gap"]["value"] < limit \
        < ctrl["max_logit_gap"]["value"]
