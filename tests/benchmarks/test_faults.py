"""The rest of a run, past the harness's look for a chip, with the timed path
broken underneath: ``correct`` has to come out false.  One case for each
fault a cell can have: a step that returns its state unchanged, half of the
batch left out with the mean taken over the rest, a token altered where it
is produced, the exchange between chips left out."""
import argparse
import importlib
import time

import jax
import pytest

import bench_paths as bp
from harness import family_transformer_lm as lm


def drive(cell_name, seconds=0.3, readings=None):
    cell = bp.cell(cell_name)
    cell.rehearse()
    args = argparse.Namespace(seed=17, seconds=seconds, trace=0,
                              rehearse=True, readings=readings)
    kind = importlib.import_module("harness.kind_" + cell.traffic["kind"])
    return kind.run(cell, args, jax.devices()[:cell.chips],
                    time.perf_counter())


def failed(result):
    return {k for k, v in result["compared"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}


def break_step(monkeypatch, wrap):
    init = lm.Trainer.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        self.step_fn = wrap(self.step_fn)
    monkeypatch.setattr(lm.Trainer, "__init__", patched)


def test_sound_run_is_correct():
    out = drive("pythia14_train")
    assert out["correct"] is True and not failed(out)


def test_state_returned_unchanged_is_caught(monkeypatch):
    def wrap(step):
        def unchanged(p, v, x, y):
            keep_p = jax.tree_util.tree_map(lambda a: a.copy(), p)
            keep_v = jax.tree_util.tree_map(lambda a: a.copy(), v)
            _p, _v, loss = step(p, v, x, y)
            return keep_p, keep_v, loss
        return unchanged
    break_step(monkeypatch, wrap)
    out = drive("pythia14_train")
    assert out["correct"] is False
    # nothing moved: the gap of the change reads 1
    assert out["compared"]["change3_norm_gap"]["value"] == pytest.approx(1.0)
    assert "change3_norm_gap" in failed(out)


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    def wrap(step):
        def half(p, v, x, y):
            n = x.shape[0] // 2
            return step(p, v, x[:n], y[:n])
        return half
    break_step(monkeypatch, wrap)
    out = drive("pythia14_train")
    assert out["correct"] is False
    assert {"loss1_rel", "grad1_norm_gap"} & failed(out)


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    from mxnet_tpu import generation

    real = generation._sample_token
    calls = {"n": 0}

    def altered(logits, *sampling):
        calls["n"] += 1
        tok = real(logits, *sampling)
        return (tok + 1) % logits.shape[-1] if calls["n"] % 5 == 0 else tok
    monkeypatch.setattr(generation, "_sample_token", altered)
    out = drive("pythia14_serve_closed", seconds=1.0)
    assert out["correct"] is False
    assert "served_logit_gap" in failed(out)


def test_the_exchange_between_chips_left_out_is_caught():
    """Planted in the reference put in the program's place: each
    row-parallel product keeps its own quarter of the sum."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    out = drive("pythia69_train_tp4", readings="no_exchange")
    assert out["correct"] is False
    assert {"loss1_rel", "grad1_norm_gap"} & failed(out)
