"""Readings behind the limit of the check: the program's widest logit gap
over many seeds, and the control's, in one process.

  python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
      --seeds 101 102 ...

For each seed it builds the cell's engine with that seed's weights, serves
the cell's own traffic for a short window, samples the finished requests as
a run does, and reads two numbers on the same prompts and served tokens:

* ``program``: the gaps between the reference's best logit and the
  reference's logit of each token the program served, as the check reads
  them (its mean is the number compared: the lower reading);
* ``control``: the same for the token that the reference computed with
  every weight rounded to float8 e4m3 puts first (the upper reading: the
  control has to fail the limit).

One JSON line per seed.  The benchmark's own runs never run the control.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import run  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    """Serve one short window of the cell and read both gaps."""
    from chipbench import harness
    from chipbench.traffic import Traffic
    m, sv = cell.config["model"], cell.config["serving"]
    engine = harness.build(cell, seed)
    harness.warm_up(engine, cell, m["hidden_size"])
    traffic = Traffic(cell.mix, seed=seed, seconds=seconds,
                      vocab=m["vocab_size"], d_model=m["hidden_size"],
                      max_batch=sv["max_batch"])
    win = harness.Window(engine, seconds, None)
    if traffic.loop == "open":
        win.open_loop(traffic.items,
                      [harness.to_request(it) for it in traffic.items])
    else:
        win.closed_loop(traffic, until_full=True)
        win.closed_loop(traffic)
    recs = win.recs
    del engine, win
    gc.collect()
    t = time.perf_counter()
    checks = harness.check(cell, recs, seed, control=True)
    return {"seed": seed, **checks["gaps"],
            "tokens": checks["tokens_compared"]["value"],
            "short_requests": checks["short_requests"]["value"],
            "check_s": time.perf_counter() - t}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    from chipbench.cells import load_cell
    cell = load_cell(args.workload)
    run.require_chips(cell.chips)
    run.enable_cache()
    for seed in args.seeds:
        line = readings(cell, seed, args.seconds)
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
