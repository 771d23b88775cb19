"""The check that decides ``correct`` catches a broken timed path.

Each test runs the rest of a run on the CPU at a tiny size, with the serving
path broken underneath, and sees ``correct`` come out false: a token altered
where it is produced, a decode step that returns its state (the KV pool)
unchanged, and, at TP=4, the exchange between chips left out.  The last
test is the control: the plain reference with every weight rounded to
float8 reads a mean logit gap above the limit that the served bfloat16
program reads under.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import _tiny
from chipbench import cells, harness, peaks
from repro.models import lm
from repro.models.api import Model

SEED = 2**33 + 29


def run_cell(tmp_path, name):
    here = _tiny.tiny_here(str(tmp_path))
    cell = cells.load_cell(name, root=here, here=here)
    return harness.run(cell, seed=SEED, seconds=3.0, trace=False,
                       t_process=0.0, devices=jax.devices()[:cell.chips],
                       peak=peaks.V5E)


def test_sound_run_is_correct(tmp_path):
    res = run_cell(tmp_path, _tiny.CLOSED)
    assert res["correct"] is True


def test_token_altered_where_produced(tmp_path, monkeypatch):
    step = Model.serve_step_paged

    def altered(self, params, cache, batch):
        logits, cache = step(self, params, cache, batch)
        return logits.at[:, 7].add(1e4), cache  # every decode emits 7

    monkeypatch.setattr(Model, "serve_step_paged", altered)
    res = run_cell(tmp_path, _tiny.CLOSED)
    assert res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > \
        res["checks"]["mean_logit_gap"]["limit"]


def test_step_returns_its_state_unchanged(tmp_path, monkeypatch):
    step = Model.serve_step_paged

    def stale(self, params, cache, batch):
        logits, _ = step(self, params, cache, batch)
        return logits, cache  # the decode step's K/V never reach the pool

    monkeypatch.setattr(Model, "serve_step_paged", stale)
    res = run_cell(tmp_path, _tiny.CLOSED)
    assert res["correct"] is False


def test_exchange_between_chips_left_out(tmp_path, monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")

    def local_only(x, w, cfg, dt):
        # each chip tiles its own shard where the others' should be
        tp = jax.lax.psum(1, cfg.tp_axis)
        full = jnp.concatenate([x] * tp, axis=-1)
        y = full @ w.astype(dt)
        return jnp.concatenate([y] * tp, axis=-1)

    monkeypatch.setattr(lm, "_col_gathered", local_only)
    res = run_cell(tmp_path, _tiny.TP)
    assert res["correct"] is False


def test_control_in_float8_fails_the_limit(tmp_path):
    """The control at a size a test holds: 8 layers of width 256, about 400
    served tokens compared.  The served program reads under the limit, the
    float8 reference over it."""
    here = _tiny.tiny_here(str(tmp_path), model=dict(
        hidden_size=256, intermediate_size=512, num_hidden_layers=8,
        vocab_size=4096))
    cell = cells.load_cell(_tiny.CLOSED, root=here, here=here)
    cell.config["serving"]["max_seq"] = 512
    cell.config["check"]["sample_requests"] = 8
    cell.mix["output_tokens"].update(min=64, max=128)
    res = harness.run(cell, seed=SEED, seconds=3.0, trace=False,
                      t_process=0.0, devices=jax.devices()[:1],
                      peak=peaks.V5E)
    from chipbench.harness import check
    assert res["correct"] is True
    # the run's sample, read again with the control beside it
    ctl = check(cell, res["recs"], SEED, control=True)
    lim = ctl["mean_logit_gap"]["limit"]
    assert ctl["gaps"]["program"]["mean"] <= lim
    assert ctl["gaps"]["control"]["mean"] > lim
