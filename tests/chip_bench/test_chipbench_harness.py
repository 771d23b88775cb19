"""The chip benchmark's harness, end to end on the CPU at a tiny size.

The command itself refuses to run without a TPU; these tests steer around
that look (and the compile cache and the table of peaks) inside the test,
and drive everything else a run does: weights from the seed, warm-up, the
measured window, the metrics and the check against the plain reference.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil

import jax
import pytest

import _tiny
from chipbench import cells, peaks

SEED = 2**33 + 17  # more than 32 bits, as a run's seed may be


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(_tiny.BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def steered(run_mod, tmp_path, monkeypatch):
    """``run.main`` on the CPU, on the tiny cells in ``tmp_path``."""
    here = _tiny.tiny_here(str(tmp_path))
    monkeypatch.setattr(cells, "ROOT", here)
    monkeypatch.setattr(cells, "HERE", here)
    monkeypatch.setattr(run_mod, "require_chips",
                        lambda n: jax.devices())
    monkeypatch.setattr(run_mod, "enable_cache", lambda: None)
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.V5E)
    return run_mod


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_refuses_to_run_without_a_tpu(run_mod, capsys):
    with pytest.raises(SystemExit) as exc:
        run_mod.main(["--workload", "qwen2-0.5b.miobench-open", "--seed",
                      "1", "--seconds", "1", "--trace", "0"])
    assert exc.value.code != 0
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and out.strip() == ""


@pytest.mark.parametrize("cell,metrics", [
    (_tiny.OPEN, {"itl_p50_ms", "itl_p95_ms", "setup_s"}),
    (_tiny.CLOSED, {"output_tok_s", "itl_p50_ms", "itl_p95_ms", "setup_s"}),
])
def test_run_reports_end_to_end_metrics_and_checks(steered, capsys, cell,
                                                   metrics):
    steered.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                  "3", "--trace", "0"])
    line = last_line(capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == len(jax.devices())
    assert list(line)[-1] == "checks"
    assert line["checks"]["mean_logit_gap"]["value"] <= \
        line["checks"]["mean_logit_gap"]["limit"]
    assert set(line["gaps"]["program"]) == {"mean", "max", "rms", "departed"}
    assert line["setup"]["window_compiles"] == 0


def test_traced_run_reports_per_layer_metrics(steered, capsys):
    steered.main(["--workload", _tiny.CLOSED, "--seed", str(SEED),
                  "--seconds", "3", "--trace", "1"])
    line = last_line(capsys)
    assert line["correct"] is True
    # the CPU has no device plane: the trace-read metrics stay silent, the
    # counter-read ones are there
    assert {"decode_batch_mean", "step_mfu"} <= set(line["metrics"])
    assert "output_tok_s" not in line["metrics"]
    assert 0 < line["metrics"]["step_mfu"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_traced_open_run_reports_its_tail_per_layer(steered, capsys):
    steered.main(["--workload", _tiny.OPEN, "--seed", str(SEED),
                  "--seconds", "3", "--trace", "1"])
    line = last_line(capsys)
    assert line["correct"] is True
    got = line["metrics"]
    assert {"ttft_p95_ms.open", "queue_wait_p95_ms",
            "prefix_hit_share"} <= set(got)
    assert "itl_p95_ms" not in got
    # first token comes after admission
    assert got["ttft_p95_ms.open"]["value"] >= \
        got["queue_wait_p95_ms"]["value"] > 0


def test_tensor_parallel_cell_on_four_devices(steered, capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    steered.main(["--workload", _tiny.TP, "--seed", str(SEED), "--seconds",
                  "3", "--trace", "0"])
    line = last_line(capsys)
    assert line["correct"] is True and "output_tok_s" in line["metrics"]


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A configuration, a mix and a per-layer metric added as new files,
    and named only in BENCHMARK.json, are found with no other edit."""
    from chipbench import harness
    here = _tiny.tiny_here(str(tmp_path / "base"))
    new = tmp_path / "new"
    shutil.copytree(here, new, symlinks=False)
    shutil.copy(new / "configs" / "tiny.json", new / "configs" / "fresh.json")
    mix = json.loads((new / "traffic" / "open.json").read_text())
    mix["arrivals"]["rate_per_s"] = 6.0
    (new / "traffic" / "fresh-mix.json").write_text(json.dumps(mix))
    (new / "metrics" / "fresh.count").write_text("")  # not a metric
    (new / "metrics" / "fresh.requests.py").write_text(
        "def read(ctx):\n    return float(len(ctx.recs))\n")
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "fresh.cell", "config": "fresh",
                               "traffic": "fresh-mix", "chips": 1,
                               "why": "added by files alone"})
    bench["per_layer"].append({"name": "fresh.requests", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "itl_p50_ms",
                               "workloads": ["fresh.cell"]})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell("fresh.cell", root=str(new), here=str(new))
    assert cell.mix["arrivals"]["rate_per_s"] == 6.0
    assert [m["name"] for m in cell.per_layer][-1] == "fresh.requests"
    res = harness.run(cell, seed=SEED, seconds=2, trace=True,
                      t_process=0.0, devices=jax.devices()[:1],
                      peak=peaks.V5E)
    assert res["metrics"]["fresh.requests"]["value"] > 0
    assert res["correct"] is True


def test_dotted_metric_falls_back_to_its_base_reader():
    """``device_idle_share.open`` and ``.closed`` share one reader file;
    a dotted name with a file of its own keeps it."""
    cell = cells.load_cell("qwen2-0.5b.miobench-open")
    base = cells.load_module("metrics", "device_idle_share")
    assert cell.metric("device_idle_share.open").__file__ == base.__file__
    assert cell.metric("tp.allgather_ms").__file__.endswith(
        os.path.join("metrics", "tp.allgather_ms.py"))


def test_arch_group_reaches_the_program_config():
    """What no Hugging Face size states comes from the file's ``arch``
    group, so a family without QKV biases needs no harness edit."""
    from chipbench import harness
    cfg = _tiny.load("configs/qwen2-0.5b.json")
    assert harness.arch_config(cfg).qkv_bias is True
    cfg["arch"] = dict(cfg["arch"], qkv_bias=False)
    arch = harness.arch_config(cfg)
    assert arch.qkv_bias is False and arch.family == "dense"
