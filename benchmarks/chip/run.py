"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, makes its weights and traffic
from the seed, warms up every shape the traffic uses, drives the serving
engine for ``--seconds``, checks a sample of what it served against the
configuration's plain reference, and prints one JSON object as the last
line of standard output.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles a stretch of the window and reports its
per-layer metrics instead.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profile into this directory")
    return ap.parse_args(argv)


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int, platform: str = "tpu"):
    """The devices the cell runs on; exits non-zero without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        print(f"run.py: needs a {platform.upper()}, found platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        sys.exit(2)
    if len(devs) < n:
        print(f"run.py: the cell needs {n} chips, found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return devs


def main(argv=None):
    args = parse(argv)
    from chipbench import harness
    from chipbench.cells import load_cell
    from chipbench.peaks import peaks
    cell = load_cell(args.workload)
    devs = require_chips(cell.chips)
    enable_cache()
    peak = peaks(devs[0].device_kind)
    res = harness.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_process=T_PROCESS,
                      devices=devs[:cell.chips], peak=peak,
                      keep_trace=args.keep_trace)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res["memory_peak_bytes"]}
    if args.trace:
        device["busy_s"] = res.get("busy_s", 0.0)
        device["window_s"] = res.get("window_s", 0.0)
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    line["setup"] = {k: res[k] for k in (
        "setup_s", "setup_split", "setup_compiles", "window_compiles",
        "queued_at_close")}
    line["gaps"] = res["gaps"]
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
