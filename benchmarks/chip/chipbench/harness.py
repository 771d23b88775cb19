"""One run of one cell: set up, warm up, measure for a fixed window, check.

The program is reached through its public entry points only:
``repro.configs.base.ArchConfig``, ``repro.models.build_model``,
``repro.distributed.tp.ShardedServing``/``serving_mesh`` and
``repro.serving.engine.ServingEngine``/``Request`` with its segments.
Weights and traffic are made by the benchmark from ``--seed``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from chipbench import stats, weights
from chipbench.cells import Cell
from chipbench.traffic import Item, Traffic

from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.segments import EmbedSegment, TextSegment

TRACE_SPAN_S = 5.0  # longest traced stretch of a window


def annotate(on: bool, name: str):
    """A host span in the profiler's trace, only in a traced run."""
    return (jax.profiler.TraceAnnotation(name) if on
            else contextlib.nullcontext())


def arch_config(config: dict) -> ArchConfig:
    """The program's configuration: the Hugging Face sizes under the
    program's names, then the file's ``arch`` group, which states what no
    size says (the family, biases, norms) in the program's own names."""
    m = config["model"]
    return ArchConfig(
        name=config["name"], n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], rope_theta=m["rope_theta"],
        tie_embeddings=m["tie_word_embeddings"], source=config["source"],
        **config["arch"])


@dataclasses.dataclass
class Rec:
    """One request of the run: what the client sent and what came back."""
    item: Item
    req: Request
    due: float = 0.0  # perf_counter seconds (open loop)
    sent: float = 0.0  # when the harness submitted it


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reads (``metrics/<name>.py``: ``read``)."""
    cell: Cell
    recs: "list[Rec]"
    t0: float
    t1: float
    counters: dict  # engine counter deltas over the window
    chips: int
    peak: dict
    trace: "object | None" = None  # chipbench.trace.Trace
    trace_t0: float = 0.0  # traced stretch on the host clock
    trace_t1: float = 0.0

    @property
    def model(self) -> dict:
        return self.cell.config["model"]

    @property
    def serving(self) -> dict:
        return self.cell.config["serving"]


class CompileCount:
    """Backend compiles, counted through JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------- set-up


def build(cell: Cell, seed: int, params=None):
    """Model, weights (one jitted call, bf16, under their shardings; or
    ``params`` from an earlier engine of the same cell and seed) and the
    engine of the cell's configuration."""
    sv = cell.config["serving"]
    cfg = arch_config(cell.config)
    model = build_model(cfg)
    ref = cell.module("references", cell.config["reference"])
    want = ref.param_shapes(cell.config["model"])
    got = {p: tuple(s.shape) for p, s in
           weights.flatten(model.abstract()).items()}
    if got != want:
        raise RuntimeError(f"the program's parameter tree {got} is not the "
                           f"reference's {want}")
    mesh, shardings = None, None
    if sv["tp"] > 1:
        from repro.distributed.tp import ShardedServing, serving_mesh
        mesh = serving_mesh(sv["tp"])
        shardings = ShardedServing(model, mesh).param_shardings
    if params is None:
        params = weights.make_params(model.abstract(), seed, shardings)
    engine = ServingEngine(
        model, params, max_batch=sv["max_batch"], max_seq=sv["max_seq"],
        page_size=sv["page_size"], prefill_chunk=sv["prefill_chunk"],
        prefill_budget=sv["prefill_budget"], kv_dtype=sv["kv_dtype"],
        prefix_caching=sv["prefix_caching"], mesh=mesh)
    return engine


def to_request(it: Item) -> Request:
    if it.media is None:
        return Request(it.uid, np.concatenate([it.prefix, it.text]),
                       max_new_tokens=it.max_new)
    segs = [TextSegment(it.prefix)] if len(it.prefix) else []
    segs += [EmbedSegment(it.media), TextSegment(it.text)]
    return Request(it.uid, segments=segs, max_new_tokens=it.max_new)


def warm_up(engine: ServingEngine, cell: Cell, d_model: int):
    """Compile what the cell's traffic will run: every prefill-chunk bucket
    (with and without an embedding span, where the mix has media) and the
    decode step; then forget the warm-up's prefixes."""
    sv = cell.config["serving"]
    rng = np.random.default_rng(0)
    buckets, b = [], 16
    while b <= sv["prefill_chunk"]:
        buckets.append(b)
        b *= 2
    media = bool(cell.mix.get("media"))
    uid = -1
    for b in buckets:
        for mm in ([False, True] if media else [False]):
            toks = rng.integers(0, engine.model.cfg.vocab, b).astype(np.int32)
            if mm:
                rows = rng.standard_normal((8, d_model)).astype(np.float32)
                req = Request(uid, segments=[EmbedSegment(rows * 0.03),
                                             TextSegment(toks[8:])],
                              max_new_tokens=2)
            else:
                req = Request(uid, toks, max_new_tokens=2)
            engine.submit(req)
            uid -= 1
    engine.run_until_drained()
    engine.reset_prefix_cache()


# ---------------------------------------------------------------- window


class Window:
    """Drives the engine for one window; ``tracing`` profiles a stretch of
    it (from 40% in, at most TRACE_SPAN_S long) into ``trace_dir``."""

    def __init__(self, engine, seconds: float, trace_dir: "str | None"):
        self.engine = engine
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.ann = trace_dir is not None
        self.recs: "list[Rec]" = []
        self.by_uid: "dict[int, Rec]" = {}
        self._win_ann = None
        self.trace_t0 = self.trace_t1 = 0.0

    def _maybe_trace(self, now: float):
        if self.trace_dir is None:
            return
        if self.trace_t0 == 0.0 and now >= self.ts:
            jax.profiler.start_trace(self.trace_dir)
            self.trace_t0 = time.perf_counter()
            self._win_ann = jax.profiler.TraceAnnotation("harness.window")
            self._win_ann.__enter__()
        elif self.trace_t0 and not self.trace_t1 and now >= self.te:
            self._stop_trace()

    def _stop_trace(self):
        self._win_ann.__exit__(None, None, None)
        self.trace_t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def submit(self, it: Item, req: Request, due: float = 0.0):
        rec = Rec(it, req, due=due, sent=time.perf_counter())
        self.engine.submit(req)
        self.recs.append(rec)
        self.by_uid[it.uid] = rec

    def start(self):
        self.t0 = time.perf_counter()
        self.t1 = self.t0 + self.seconds
        span = min(TRACE_SPAN_S, self.seconds / 3)
        self.ts = self.t0 + 0.4 * self.seconds
        self.te = self.ts + span

    def finish(self):
        if self.trace_t0 and not self.trace_t1:
            self._stop_trace()

    def open_loop(self, items: "list[Item]", reqs: "list[Request]"):
        eng, i, n = self.engine, 0, len(items)
        self.start()
        while True:
            now = time.perf_counter()
            if now >= self.t1:
                break
            self._maybe_trace(now)
            if i < n and self.t0 + items[i].due <= now:
                with annotate(self.ann, "harness.submit"):
                    while i < n and self.t0 + items[i].due <= now:
                        self.submit(items[i], reqs[i],
                                    due=self.t0 + items[i].due)
                        i += 1
            if eng.busy():
                with annotate(self.ann, "engine.step"):
                    eng.step()
            else:
                nxt = self.t0 + items[i].due if i < n else self.t1
                with annotate(self.ann, "harness.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, self.t1) - now))
        self.finish()
        # due in the window but never sent: they wait until it closes
        for it, req in zip(items[i:], reqs[i:]):
            if it.due < self.seconds:
                self.recs.append(Rec(it, req, due=self.t0 + it.due))

    def closed_loop(self, traffic: Traffic, until_full: bool = False):
        """Each client sends its next request as soon as its last one
        finishes.  ``until_full``: the set-up phase, stepping until every
        slot decodes (no window, no trace), with no cap on prefill tokens a
        tick so that the slots fill in a few ticks; the window runs under
        the configured cap."""
        eng = self.engine
        if until_full:
            for c in range(traffic.clients):
                it = traffic.next_for(c)
                self.submit(it, to_request(it))
            self._seen = len(eng.finished)
            budget = eng.prefill_budget
            eng.prefill_budget = eng.max_batch * eng.max_seq
            while not (all(s is not None for s in eng.slots)
                       and all(t is None for t in eng.prefill_tasks)):
                eng.step()
                self._resubmit(traffic)
            eng.prefill_budget = budget
            return
        self.start()
        while True:
            now = time.perf_counter()
            if now >= self.t1:
                break
            self._maybe_trace(now)
            with annotate(self.ann, "engine.step"):
                eng.step()
            with annotate(self.ann, "harness.submit"):
                self._resubmit(traffic)
        self.finish()

    def _resubmit(self, traffic: Traffic):
        fin = self.engine.finished
        for req in fin[self._seen:]:
            rec = self.by_uid.get(req.uid)
            if rec is not None:
                it = traffic.next_for(rec.item.client)
                self.submit(it, to_request(it))
        self._seen = len(fin)


# ---------------------------------------------------------------- metrics


def counters(engine) -> dict:
    s = engine.metrics.snapshot()
    return {k: v for k, v in s.items() if isinstance(v, (int, float))}


def end_to_end(cell: Cell, recs: "list[Rec]", t0: float, t1: float
               ) -> dict:
    """The cell's end-to-end metrics, from the harness's own clocks."""
    out = {}
    names = {m["name"] for m in cell.end_to_end}
    itl = []
    for r in recs:
        itl += stats.itl_samples(r.req.token_times, r.req.done, t0, t1)
    if "itl_p50_ms" in names:
        v = stats.percentile(itl, 50)
        out["itl_p50_ms"] = None if v is None else v * 1e3
    if "itl_p95_ms" in names:
        v = stats.percentile(itl, 95)
        out["itl_p95_ms"] = None if v is None else v * 1e3
    if "output_tok_s" in names:
        n = sum(stats.tokens_in(r.req.token_times, t0, t1) for r in recs)
        out["output_tok_s"] = n / (t1 - t0)
    return out


# ---------------------------------------------------------------- check


def sample(recs: "list[Rec]", n: int, seed: int) -> "list[Rec]":
    """A sample of the finished requests, drawn from the seed: the one with
    the most served tokens, then one of each kind (media kind, and first
    ask or a repeat), then the rest at random."""
    done = [r for r in recs if r.req.done]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
    longest = max(done, key=lambda r: (len(r.req.output), -r.item.uid))
    picked = {longest.item.uid: longest}
    kinds: dict = {}
    for r in done:
        kinds.setdefault((r.item.media_kind, r.item.ask > 0), []).append(r)
    for key in sorted(kinds, key=str):
        cand = [r for r in kinds[key] if r.item.uid not in picked]
        if cand and len(picked) < n:
            r = cand[int(rng.integers(len(cand)))]
            picked[r.item.uid] = r
    rest = [r for r in done if r.item.uid not in picked]
    for j in rng.permutation(len(rest))[:max(0, n - len(picked))]:
        picked[rest[j].item.uid] = rest[j]
    return list(picked.values())


def reference_seqs(picked: "list[Rec]") -> "list[dict]":
    """What the reference is given: the client's own prompt (ids, and the
    media rows where the span sits) and the served tokens."""
    seqs = []
    for r in picked:
        it, out = r.item, list(r.req.output)
        rows = 0 if it.media is None else len(it.media)
        ids = np.concatenate([it.prefix, np.zeros(rows, np.int32), it.text,
                              np.asarray(out[:-1], np.int32)])
        mask = np.zeros(len(ids), bool)
        mask[len(it.prefix):len(it.prefix) + rows] = True
        seqs.append({"ids": ids, "n_prompt": it.prompt_len, "served": out,
                     "mask": mask, "rows": it.media})
    return seqs


def gap_stats(gaps) -> dict:
    """Over every compared token: the mean gap, the widest, the root mean
    square and the share of tokens that are not the reference's best."""
    a = np.concatenate(gaps) if gaps else np.zeros(0)
    if a.size == 0:
        return {"mean": float("inf"), "max": float("inf"),
                "rms": float("inf"), "departed": 1.0}
    return {"mean": float(a.mean()), "max": float(a.max()),
            "rms": float(np.sqrt((a * a).mean())),
            "departed": float((a > 0).mean())}


def check(cell: Cell, recs: "list[Rec]", seed: int, control: bool = False
          ) -> dict:
    """Compare a sample of what the window served with the configuration's
    plain reference.  Returns the numbers compared, each with its limit,
    and under ``gaps`` the statistics of the served tokens' logit gaps
    (and, with ``control``, of the float8 control's on the same tokens).

    The number compared is the mean gap: the widest gap does not part the
    served bfloat16 program from the float8 control at full width, since
    both flip only near-ties and their largest flips lie close together;
    the mean weighs how often and how far each departs."""
    cfg = cell.config
    lim = cfg["check"]
    picked = sample(recs, int(lim["sample_requests"]), seed)
    short = sum(1 for r in recs if r.req.done
                and len(r.req.output) != r.item.max_new)
    ref_mod = cell.module("references", cfg["reference"])
    src = weights.Source(ref_mod.param_shapes(cfg["model"]), seed)
    ref = ref_mod.Reference(cfg["model"], src,
                            max_seq=cfg["serving"]["max_seq"])
    seqs = reference_seqs(picked)
    if seqs:
        res = ref.gaps(seqs, control=control)
    else:
        res = ([], []) if control else []
    g = res[0] if control else res
    stats_g = gap_stats(g)
    out = {
        "mean_logit_gap": {"value": stats_g["mean"],
                           "limit": float(lim["mean_logit_gap"])},
        "short_requests": {"value": short, "limit": 0},
        "tokens_compared": {"value": int(sum(len(x) for x in g)),
                            "limit": int(lim["min_tokens_compared"])},
        "gaps": {"program": stats_g},
    }
    if control:
        out["gaps"]["control"] = gap_stats(res[1])
    return out


def passed(checks: dict) -> bool:
    c = checks
    return (c["mean_logit_gap"]["value"] <= c["mean_logit_gap"]["limit"]
            and c["short_requests"]["value"] <= 0
            and c["tokens_compared"]["value"]
            >= c["tokens_compared"]["limit"])


# ---------------------------------------------------------------- run


def peak_memory(devices) -> int:
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_process: float, devices, peak: dict,
        keep_trace: "str | None" = None) -> dict:
    """The whole run; returns the result line's fields."""
    from chipbench import trace as tr
    sv, m = cell.config["serving"], cell.config["model"]
    compiles = CompileCount()
    split = {"start": time.time() - t_process}  # seconds into set-up

    def mark(name):
        split[name] = time.time() - t_process - sum(split.values())

    engine = build(cell, seed)
    mark("weights_engine")
    warm_up(engine, cell, m["hidden_size"])
    mark("warm_up")
    traffic = Traffic(cell.mix, seed=seed, seconds=seconds,
                      vocab=m["vocab_size"], d_model=m["hidden_size"],
                      max_batch=sv["max_batch"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    win = Window(engine, seconds, trace_dir)
    if traffic.loop == "open":
        reqs = [to_request(it) for it in traffic.items]
    else:
        win.closed_loop(traffic, until_full=True)
    mark("requests")
    c_before = counters(engine)
    n_compiles = compiles.n
    t_setup = time.time() - t_process
    if traffic.loop == "open":
        win.open_loop(traffic.items, reqs)
        attempted = sum(1 for it in traffic.items if it.due < seconds)
    else:
        win.closed_loop(traffic)
        attempted = sum(1 for r in win.recs if win.t0 <= r.sent < win.t1)
    window_compiles = compiles.n - n_compiles
    c_after = counters(engine)
    mem = peak_memory(devices)
    deltas = {k: c_after[k] - c_before.get(k, 0) for k in c_after}
    recs = win.recs
    ctx = Ctx(cell=cell, recs=recs, t0=win.t0, t1=win.t1, counters=deltas,
              chips=cell.chips, peak=peak, trace_t0=win.trace_t0,
              trace_t1=win.trace_t1)
    queued = len(engine.queue)
    # free the program's weights and pool before the reference runs
    del engine, win
    gc.collect()
    result = {"attempted": attempted, "failed": 0,
              "memory_peak_bytes": mem, "setup_s": t_setup,
              "window_compiles": window_compiles, "queued_at_close": queued,
              "setup_split": split, "setup_compiles": n_compiles}
    if trace:
        path = tr.find_xplane(trace_dir)
        ctx.trace = tr.load_xplane(path) if path else None
        if keep_trace and path:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(keep_trace, os.path.basename(
                path)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for spec in cell.per_layer:
            v = cell.metric(spec["name"]).read(ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        result["metrics"] = metrics
        t = ctx.trace
        if t is not None and t.devices:
            result["busy_s"] = tr.mean_busy_s(t)
            result["window_s"] = t.window_s
            dev = t.devices[0]
            result["breakdown"] = {"device_ops": tr.op_totals(t, dev),
                                   "idle_gaps": tr.idle_gaps(t, dev)}
    else:
        e2e = end_to_end(cell, recs, ctx.t0, ctx.t1)
        e2e["setup_s"] = t_setup
        units = {s["name"]: s["unit"] for s in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items() if v is not None}
    result["checks"] = check(cell, recs, seed)
    result["gaps"] = result["checks"].pop("gaps")
    result["correct"] = passed(result["checks"])
    result["recs"] = recs
    return result

