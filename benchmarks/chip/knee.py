"""Find the highest rate an open-loop cell sustains: a sweep on the chip.

  python3 benchmarks/chip/knee.py --workload <open-loop cell> --seed <n> \\
      --seconds <s> --rates 4 8 16 ...

One process: the weights are made once; each rate gets a fresh engine,
warmed up, and is offered for ``--seconds`` with the cell's own mix
(``rate_per_s`` replaced).  For each rate it prints one JSON line: requests
due, finished, the backlog (queued and prefilling requests) averaged over
each quarter of the window, and the p95 time to first token.  The knee is the highest rate
whose backlog does not grow from the first quarter to the last; the mix's
``rate_per_s`` is then set to four fifths of it by hand.
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    from chipbench import harness, stats
    from chipbench.cells import load_cell
    from chipbench.traffic import Traffic
    cell = load_cell(args.workload)
    run.require_chips(cell.chips)
    run.enable_cache()
    m, sv = cell.config["model"], cell.config["serving"]
    params = None
    for rate in args.rates:
        engine = harness.build(cell, args.seed, params)
        params = engine.params
        harness.warm_up(engine, cell, m["hidden_size"])
        traffic = Traffic(cell.mix, seed=args.seed, seconds=args.seconds,
                          vocab=m["vocab_size"], d_model=m["hidden_size"],
                          max_batch=sv["max_batch"], rate_per_s=rate)
        reqs = [harness.to_request(it) for it in traffic.items]
        win = harness.Window(engine, args.seconds, None)
        backlog = []
        step = engine.step

        def sampled_step():
            backlog.append((time.perf_counter(), len(engine.queue) + sum(
                t is not None for t in engine.prefill_tasks)))
            return step()

        engine.step = sampled_step
        win.open_loop(traffic.items, reqs)
        engine.step = step
        t0, t1 = win.t0, win.t1
        q = [[b for t, b in backlog if t0 + i * (t1 - t0) / 4 <= t
              < t0 + (i + 1) * (t1 - t0) / 4] for i in range(4)]
        ttft = stats.percentile(stats.ttft_samples(
            [(r.due, r.req.token_times) for r in win.recs], t0, t1), 95)
        line = {"rate_per_s": rate, "due": len(win.recs),
                "finished": sum(r.req.done for r in win.recs),
                "backlog_by_quarter": [sum(x) / len(x) if x else None
                                       for x in q],
                "ttft_p95_ms": None if ttft is None else ttft * 1e3,
                "ticks": len(backlog)}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        del engine, win, sampled_step, step
        gc.collect()


if __name__ == "__main__":
    main()
