"""CPU rehearsal of ``chip_smoke.py``: its phase functions at ``reduced()``
size, with the checks that need a chip steered here.  On the CPU the models
take the XLA gather path, so the Mosaic-kernel check is replaced by one that
only lowers the step it was handed; the script itself has no CPU mode."""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lowered = []
    with pytest.MonkeyPatch.context() as mp:
        # the persistent compile cache belongs to the script's own runs
        mp.setattr(mod, "enable_compile_cache", lambda: None)
        mp.setattr(mod, "NEW_TOKENS", 8)
        mp.setattr(mod, "DRAFT_LAYERS", 1)
        mp.setattr(mod, "KV_BUDGET_BYTES", 16 << 20)
        mp.setattr(mod, "check_mosaic",
                   lambda step, *args, what: lowered.append(
                       (what, step.lower(*args))))
        mod.lowered = lowered
        yield mod


@pytest.fixture(scope="module")
def qwen(smoke):
    cfg = reduced(get_config("qwen2-0.5b"))
    params = build_model(cfg).init(jax.random.PRNGKey(smoke.SEED))
    return cfg, params, smoke.Reference(cfg, params)


def test_main_refuses_cpu(smoke, capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err and '"ok"' not in out


@pytest.mark.parametrize("phase", ["A", "B", "C"])
def test_phase_serve(smoke, qwen, phase):
    cfg, params, ref = qwen
    kw = {"B": {"kv_dtype": "int8"}, "C": {"speculative": True}}.get(phase,
                                                                    {})
    n_lowered = len(smoke.lowered)
    out = smoke.phase_serve(phase, cfg, params, ref, **kw)
    assert out["requests"] == len(smoke.TEXT_PROMPTS) + 3
    assert out["tokens_out"] == out["requests"] * smoke.NEW_TOKENS
    assert out["prefix_tokens_reused"] >= smoke.SHARED_PREFIX - smoke.PAGE
    assert out["max_logit_err"] <= out["logit_tol"]
    steps = [what for what, _ in smoke.lowered[n_lowered:]]
    assert steps == ([f"{phase} decode step", f"{phase} verify step"]
                     if phase == "C" else [f"{phase} decode step"])
    if phase == "A":
        assert out["router_ok"] == smoke.ROUTER_TASKS
    if phase == "C":
        assert out["acceptance_rate"] is not None


def test_phase_tp(smoke):
    cfg = reduced(get_config("codeqwen1.5-7b"))
    full = smoke.phase_tp_full(cfg, 4)
    assert full["tokens_out"] == len(smoke.TP_PROMPTS) * smoke.NEW_TOKENS
    cmp = smoke.phase_tp_compare(dataclasses.replace(cfg, n_layers=2), 4)
    assert cmp["token_identical"] == len(smoke.TP_PROMPTS)
    assert cmp["max_logit_diff"] <= cmp["logit_tol"]
