"""Slot-based continuous-batching serving engine with a paged KV cache.

A fixed decode batch of ``max_batch`` slots steps in lockstep (one
``serve_step`` per tick).  Arriving requests are prefilled into a free slot;
finished slots are freed immediately, so long requests never block short
ones (continuous batching).

Two cache backends:

  * **paged** (default for the pure-attention family) — K/V live in a
    shared page pool (``repro/serving/kv_cache.py``); each slot holds a
    block table instead of a dense ``max_seq`` region, freed requests
    return their pages, and identical prompt prefixes across requests are
    served from the prefix trie without recomputation (copy-on-write).
    ``kv_dtype="int8"`` stores the pool quantized (symmetric per-row
    int8 + fp32 scales, ``repro/kernels/quant.py``): decode streams half
    the KV bytes per tick through the fused-dequant kernels, and a fixed
    ``kv_budget_bytes`` buys ~2x the pages — so admission control sees a
    doubled page budget on edge-sized devices.
  * **dense** — the original one-region-per-slot layout, still used for
    recurrent/hybrid/cross-attention cache families (zamba2, xlstm,
    whisper) whose state is not an append-only token sequence.

Decode-loop overhead: the jitted decode and chunked-prefill steps donate
their cache argument (``donate_argnums``), so XLA updates the pool
in-place instead of copying the full KV cache every tick, and the decode
step argmaxes on device — one ``[B]`` int32 token-id transfer per tick
instead of ``[B, vocab]`` logits (``return_logits=True`` restores the
logits for tests).

Prefill scheduling (attention family): prompts are **shape-bucketed** —
right-padded to power-of-two lengths with the true length threaded through
``Model.prefill``/``prefill_chunk_*`` — so a mixed-length workload traces
O(log max_seq) XLA variants instead of one per distinct prompt length, and
**chunked** — long prompts append into the cache ``prefill_chunk`` tokens
at a time under a per-tick ``prefill_budget``, sharing ticks with decode
steps so a long prompt no longer stalls every running decode for its whole
prefill (mixed prefill/decode continuous batching).  Recurrent/hybrid
families keep exact-shape monolithic prefill: their state integrates every
input token, so padding would corrupt it.

Multimodal requests (attention family): a ``Request`` may carry typed
``segments`` (repro/serving/segments.py) — text token spans interleaved
with precomputed embedding spans (image patches / audio frames from
repro/models/mm_encoder.py).  The engine books everything (lengths,
buckets, the prefix trie) against the per-position *key ids* (token ids /
negative content-digest ids), and hands the embedding rows + injection
mask to the prefill entry points, which embed-and-inject once at the
boundary (``lm.embed_inputs``).  Two requests carrying the same image hit
each other's prefix-cache blocks exactly like identical text would.

Disaggregated prefill/decode (paged path): a decoding request can be
checkpointed as a portable ``KVSnapshot`` (``export_kv``) or evacuated
between ticks (``evacuate``), and a snapshot-carrying request submitted
to another engine is admitted *straight into decode phase* — its pages
adopted into the local pool (converted to the local ``kv_dtype``), its
prompt blocks re-registered in the prefix trie, no prefill pass — and
resumes at exactly ``output[-1]``.  The continuum harness
(repro/serving/cluster.py) charges the transfer on the device link under
its virtual clock.

Works for every arch family — per-leaf cache batch dims are keyed by the
cache layout names in repro/models/api.py.

Observability (repro/serving/telemetry.py): every engine owns a
``MetricsRegistry`` (request/token counters, TTFT/ITL/e2e histograms,
KV-pool and XLA-trace views) — ``latency_stats()``/``stats()`` are thin
views over it.  Passing ``telemetry=`` additionally records request
lifecycle spans (submit→queue→prefill-chunk[i]→decode→finish) and
per-tick batch/KV-occupancy counter samples against the engine's clock,
exportable as Perfetto-loadable Chrome trace JSON
(``Telemetry.export``).  With ``telemetry=None`` (default) the decode
hot path performs no tracing work at all beyond plain counter adds.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant import dequantize_kv, quantize_kv
from repro.models.api import Model
from repro.serving import segments as sg
from repro.serving.kv_cache import (BlockPool, BlockTable, KVSnapshot,
                                    OutOfPagesError, ceil_blocks,
                                    full_blocks, kv_page_bytes)
from repro.serving.request import ContinuumRequest, StreamEvent
from repro.serving.telemetry import MetricsRegistry, latency_summary


def bucket_length(n: int, *, minimum: int = 16, maximum: int | None = None
                  ) -> int:
    """Smallest power-of-two >= n, clamped to [minimum, maximum].

    Prefill shapes are padded to these buckets so the number of distinct
    XLA traces is O(log max_seq) rather than one per prompt length.
    """
    if n < 1:
        raise ValueError(f"bucket_length needs n >= 1, got {n}")
    if maximum is not None and n > maximum:
        raise ValueError(f"bucket_length: n={n} exceeds maximum={maximum}")
    b = max(minimum, 1 << (n - 1).bit_length())
    return b if maximum is None else min(b, maximum)

# batch-dim index per cache leaf name (see Model.abstract_cache layouts)
_BATCH_DIM = {"k": 1, "v": 1, "xk": 1, "xv": 1, "pos_map": 0,
              "conv": 2, "ssm": 2, "mconv": 2, "mC": 2, "mn": 2, "mm": 2,
              "sc": 1, "sn": 1, "sm": 1, "sh": 1}
# leaves whose (L, B, S, ...) seq dim must be grown to max_seq on insert
_SEQ_DIM = {"k": 2, "v": 2, "pos_map": 1}


@dataclasses.dataclass
class Request:
    uid: int
    # prompt token ids; for a multimodal request (``segments`` given) this
    # is derived automatically: the per-position bookkeeping *key ids*
    # (text token ids, negative content-digest ids for embedding
    # positions — repro/serving/segments.py), which drive prompt length,
    # bucket shapes and the paged prefix-cache trie uniformly
    tokens: np.ndarray | None = None
    max_new_tokens: int = 32
    extra: dict | None = None  # e.g. encoder_frames for whisper
    # ordered modality spans (TextSegment / EmbedSegment); None = text-only
    segments: "list | None" = None
    # filled during serving:
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_admit: float = 0.0  # when prefill work started (ends the queue span)
    token_times: list = dataclasses.field(default_factory=list)
    # float32 [vocab] next-token logits behind each ``output`` token, kept
    # only by an engine built with ``return_logits=True``
    logits: list = dataclasses.field(default_factory=list, repr=False)
    # derived for multimodal requests: [T, d] float32 embedding rows and
    # the [T] bool injection mask handed to the model entry points
    features: np.ndarray | None = dataclasses.field(default=None,
                                                    repr=False)
    embed_mask: np.ndarray | None = dataclasses.field(default=None,
                                                      repr=False)
    # checkpointed KV state from another engine (kv_cache.KVSnapshot): the
    # request is admitted straight into decode phase from these pages —
    # no prefill pass — resuming at exactly ``output[-1]``
    imported: "KVSnapshot | None" = dataclasses.field(default=None,
                                                      repr=False)
    # per-token delivery callback (StreamEvent per decoded token, emitted
    # inside step() as the token is sampled); None = drain-based only.
    # Survives evacuate/resubmit, so a mid-stream migration keeps
    # streaming to the same consumer with contiguous indices.
    stream: "Callable[[StreamEvent], None] | None" = \
        dataclasses.field(default=None, repr=False)
    # admission-group id under the saxml batching knobs (None = admitted
    # on the legacy unrestricted path); engine-internal
    group: "int | None" = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        if self.segments is None:
            return
        self.tokens = sg.key_ids(self.segments)
        media = sg.media_segments(self.segments)
        if media:
            d = np.asarray(media[0].features).shape[-1]
            self.features, self.embed_mask = sg.dense_features(
                self.segments, d)

    def ttft_s(self) -> float:
        """Time-to-first-token (prefill + queueing), on the engine clock."""
        return self.token_times[0] - self.t_submit

    def itl_s(self) -> list:
        """Inter-token latencies of the decode phase (engine clock)."""
        return [b - a for a, b in zip(self.token_times, self.token_times[1:])]

    def e2e_s(self) -> float:
        """Submit-to-last-token latency, on the engine clock."""
        return self.token_times[-1] - self.t_submit


@dataclasses.dataclass
class _PrefillTask:
    """In-flight chunked prefill of one slot (prompt partially in cache)."""
    req: Request
    done: int  # prompt tokens already in the cache (incl. prefix reuse)
    reused: int = 0  # prefix-cache tokens among ``done``
    logits: Any = None  # last chunk's next-token logits [1, V]


class ServingEngine:
    def __init__(self, model: Model, params, *, max_batch: int = 4,
                 max_seq: int = 256, eos_id: int | None = None,
                 greedy: bool = True, paged: bool | None = None,
                 page_size: int = 16, num_pages: int | None = None,
                 kv_dtype: str = "bf16", kv_budget_bytes: int | None = None,
                 prefix_caching: bool = True, prefill_chunk: int = 64,
                 prefill_budget: int | None = None,
                 bucket_prompts: bool = True, min_bucket: int = 16,
                 return_logits: bool = False,
                 draft_config=None, draft_params=None, draft_seed: int = 0,
                 spec_k: int = 3,
                 sorted_batch_sizes: "list[int] | None" = None,
                 max_live_batches: "int | None" = None,
                 batching_wait_secs: float = 0.0,
                 clock: "Callable[[], float] | None" = None,
                 telemetry=None, trace_name: str = "engine",
                 mesh=None):
        """``prefill_chunk`` — tokens appended to the cache per chunked
        prefill call (0 disables chunking: one monolithic, still bucketed,
        prefill per admission).  ``prefill_budget`` — prefill tokens spent
        per engine tick before the decode step runs (default
        ``2 * prefill_chunk``); bounds how long any prompt can stall
        running decodes.  ``bucket_prompts`` — pad prompt (and chunk)
        shapes to power-of-two buckets >= ``min_bucket`` so XLA compiles
        O(log max_seq) prefill variants instead of one per prompt length.
        Both knobs apply to the attention family only; recurrent/hybrid
        caches always use exact-shape monolithic prefill.

        ``kv_dtype`` — precision of the paged KV pool: ``"bf16"``
        (default, token-identical to the historical engine) or ``"int8"``
        (quantized pages + fp32 scale rows, fused-dequant decode; paged
        backend only).  ``kv_budget_bytes`` — size the page pool to a
        device KV byte budget instead of the worst-case slot count: the
        pool gets ``budget // page_bytes()`` pages, so the same budget
        admits ~2x the pages under int8 (the admission-control headroom
        the continuum's edge tiers trade precision for).

        ``return_logits`` — the decode and verify steps normally argmax on
        device and return token ids (one int32 per slot per tick over the
        host link); True restores the full ``[B, vocab]`` logits transfer
        and keeps each generated token's logits on ``Request.logits``, for
        tests and for comparing served logits with a reference.

        ``draft_config`` — an ``ArchConfig`` for a small draft model
        turns on **speculative decoding** (paged backend only): each
        tick the draft model proposes ``spec_k`` tokens per active slot
        (dense draft cache, one cheap decode step per proposal), the
        target model scores all of them in *one* multi-token verify pass
        (``Model.verify_step_paged`` over the Pallas paged-verify
        kernel, amortized across the batch), and the longest agreeing
        prefix plus the target's correction token is emitted — 1 to
        ``spec_k + 1`` tokens per slot per tick, **bit-identical** to
        plain greedy decode regardless of draft quality.  Rejected
        draft positions keep their scattered K/V: they sit past the
        accepted position, every causal read masks them, and the next
        tick overwrites them — rollback is positional, never a page
        copy.  ``draft_params`` supplies the draft weights (default: a
        fresh init from ``draft_seed``).  The draft model must be
        attention-family with the same vocab as the target.

        ``sorted_batch_sizes`` / ``max_live_batches`` /
        ``batching_wait_secs`` — saxml-style admission batching (the
        ``ServableMethod`` knobs).  None (default) keeps the legacy
        per-request admission.  With a sorted list of allowed admission
        batch sizes, queued requests are admitted in *groups*: as soon
        as the queue can fill the largest bucket ``<= len(queue)``, that
        many are admitted together; a partial group is only released
        once the oldest queued request has waited ``batching_wait_secs``
        on the engine clock (so admission delay is bounded), and is
        padded *conceptually* to the smallest bucket ``>= count`` (the
        group never exceeds its bucket).  ``max_live_batches`` caps how
        many admitted groups may be in flight (prefilling or decoding)
        at once; further admission holds until a group fully finishes.

        ``clock`` — time source for request timestamps (``t_submit`` /
        ``token_times``).  Default is ``time.perf_counter`` (wall clock); an
        external driver stepping this engine tick-by-tick (the cloud-edge
        continuum harness, repro/serving/cluster.py) passes its virtual
        clock instead, so ``latency_stats()`` reports TTFT/ITL/e2e in
        virtual-clock seconds rather than host wall time.

        ``mesh`` — a ``jax.sharding.Mesh`` with a ``model`` axis
        (``repro.distributed.tp.serving_mesh``) turns on tensor-parallel
        serving: weights and the paged KV pool are sharded across the
        mesh (``distributed/tp.ShardedServing``) and every hot jitted
        step runs under ``shard_map``.  Paged backend only.  Host-side
        page bookkeeping (CoW, scatters, snapshot export/import) indexes
        the unsharded page axis, so prefix caching, migration and
        speculative decoding all work unchanged; the draft model stays
        unsharded (draft/verify traffic crosses the host anyway).

        ``telemetry`` — optional ``repro.serving.telemetry.Telemetry``.
        When given (and its tracer enabled), the engine records request
        lifecycle spans and per-tick occupancy counter samples against its
        clock under process ``trace_name``, and registers its metrics
        registry for export.  ``None`` keeps tracing fully off: the hot
        path does a single ``is None`` check and no event allocation.
        """
        self.model = model
        self.params = params
        self._now = clock if clock is not None else time.perf_counter
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * max_batch
        self.pos = np.zeros(max_batch, np.int64)  # next position per slot
        self.budget = np.zeros(max_batch, np.int64)
        self.paged = model.supports_paged if paged is None else paged
        if self.paged and not model.supports_paged:
            raise ValueError(
                f"{model.cfg.name}: paged serving needs an attention-family "
                "cache; use paged=False")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}")
        if kv_dtype != "bf16" and not self.paged:
            raise ValueError(
                "kv_dtype='int8' needs the paged cache backend (dense/"
                "recurrent caches stay bf16)")
        self.kv_dtype = kv_dtype
        # ---- tensor-parallel serving (mesh= -> shard_map'd jit surface)
        self.mesh = mesh
        if mesh is not None:
            if not self.paged:
                raise ValueError(
                    "mesh= (tensor-parallel serving) needs the paged cache "
                    "backend; use paged=True")
            from repro.distributed.tp import ShardedServing
            self._tp = ShardedServing(model, mesh)
            self.params = self._tp.shard_params(params)
        else:
            self._tp = None
        serving = self._tp if self._tp is not None else model
        self.return_logits = return_logits
        self.bucketing = bucket_prompts and model.supports_bucketed_prefill
        self.chunked = prefill_chunk > 0 and model.supports_chunked_prefill
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = (prefill_budget if prefill_budget is not None
                               else 2 * max(prefill_chunk, 1))
        self.min_bucket = min_bucket
        self.prefill_tasks: list[_PrefillTask | None] = [None] * max_batch
        # ---- saxml-style admission batching (None = legacy per-request)
        if sorted_batch_sizes is not None:
            sizes = sorted(set(int(b) for b in sorted_batch_sizes))
            if not sizes or sizes[0] < 1:
                raise ValueError("sorted_batch_sizes needs sizes >= 1, got "
                                 f"{sorted_batch_sizes!r}")
            if sizes[-1] > max_batch:
                raise ValueError(
                    f"sorted_batch_sizes max {sizes[-1]} exceeds "
                    f"max_batch={max_batch}")
            sorted_batch_sizes = sizes
        self.sorted_batch_sizes = sorted_batch_sizes
        self.max_live_batches = max_live_batches
        self.batching_wait_secs = float(batching_wait_secs)
        self._group_left: dict[int, int] = {}  # group id -> unfinished
        self._next_group = 0
        self._admit_quota: "int | None" = None  # per-tick, set in step()
        self._cur_group: "int | None" = None
        self._admission_held = False  # tick ended with queue held back
        self._traced: set = set()  # distinct prefill-path trace shapes
        self._prefill = jax.jit(serving.prefill)
        # ---- metrics registry: counters the hot paths increment directly
        # (bound attributes, no dict lookups), everything else views/hists.
        # latency_stats()/stats() are thin views over this registry.
        self.telemetry = telemetry
        self.metrics = m = MetricsRegistry()
        self._c_prefill_computed = m.counter("prefill_tokens_computed")
        self._c_prefill_padded = m.counter("prefill_tokens_padded")
        self._c_prefix_reused = m.counter("prefix_tokens_reused")
        self._c_submitted = m.counter("requests_submitted")
        self._c_finished = m.counter("requests_finished")
        self._c_decode_tokens = m.counter("decode_tokens")
        # KV snapshot traffic (disaggregated prefill/decode): pages and
        # bytes exported to / imported from other engines, at this
        # engine's own page precision
        self._c_kv_exported_pages = m.counter("kv_exported_pages")
        self._c_kv_imported_pages = m.counter("kv_imported_pages")
        self._c_kv_export_bytes = m.counter("kv_export_bytes")
        self._c_kv_import_bytes = m.counter("kv_import_bytes")
        # new XLA traces since the last metrics.reset() — the steady-state
        # recompile guard asserts this stays 0 on a warmed engine
        self._c_trace_events = m.counter("xla_trace_events")
        self._h_ttft = m.histogram("ttft_s")
        self._h_itl = m.histogram("itl_s")
        self._h_e2e = m.histogram("e2e_s")
        self._h_queue = m.histogram("queue_s")
        # fraction of the per-tick prefill token budget actually spent
        # (can slightly exceed 1.0: chunks are charged at bucket size);
        # observed only on ticks that did prefill work, telemetry only
        self._h_budget_util = m.histogram("prefill_budget_util")
        # admission-group sizes under the saxml batching knobs, and the
        # streamed-token counter (0 for drain-only workloads)
        self._h_admit_size = m.histogram("batch_admit_size")
        self._c_stream_tokens = m.counter("stream_tokens")
        # speculative decoding: drafted = spec_k per active slot per tick;
        # accepted = drafts consumed into the output stream; wasted =
        # drafted - accepted (verify compute spent on rejected tokens).
        # acceptance_rate() and the router's spec-shape pricing read these.
        self._c_spec_drafted = m.counter("spec_tokens_drafted")
        self._c_spec_accepted = m.counter("spec_tokens_accepted")
        self._c_spec_wasted = m.counter("spec_tokens_wasted")
        self._g_accept_rate = m.gauge("spec_acceptance_rate")
        self._g_queue_depth = m.gauge("queue_depth")
        m.view("ticks", lambda: self.ticks)
        m.view("kv_cache_bytes", self.kv_cache_bytes)
        m.view("prefill_trace_count", self.prefill_trace_count)
        tr = telemetry.tracer if telemetry is not None else None
        self._tr = tr if (tr is not None and tr.enabled) else None
        self._pid = self._tr.process(trace_name) if self._tr else 0
        if telemetry is not None:
            telemetry.register_metrics(trace_name, m)
        if self.paged:
            self.page_size = page_size
            self.max_blocks = ceil_blocks(max_seq, page_size)
            if num_pages is None:
                if kv_budget_bytes is not None:
                    # device KV byte budget -> page count at this
                    # precision: int8 pages are ~half the bytes, so the
                    # same budget admits ~2x the pages
                    num_pages = max(2, 1 + kv_budget_bytes
                                    // self.page_bytes())
                else:
                    # worst case (== dense capacity): admission/decode can
                    # never run out; size smaller to trade safety for
                    # memory
                    num_pages = 1 + max_batch * self.max_blocks
            self.prefix_caching = prefix_caching
            self.pool = BlockPool(num_pages, page_size)
            # pool occupancy/hit/eviction/CoW stats as live registry views
            # (survive reset_prefix_cache swapping the pool object)
            for key in ("num_pages", "block_size", "pages_in_use",
                        "pages_cached", "prefix_hits", "prefix_misses",
                        "evictions", "cow_copies"):
                m.view(key, lambda k=key: self.pool.stats()[k])
            abstract = model.abstract_paged_cache(num_pages, page_size,
                                                  kv_dtype=kv_dtype)
            # created under its sharding: each device allocates only its
            # own shard, nothing is built whole on one device first
            shardings = ({} if self._tp is None
                         else self._tp.cache_shardings(abstract))
            self.cache = {name: jnp.zeros(s.shape, s.dtype,
                                          device=shardings.get(name))
                          for name, s in abstract.items()}
            self.tables = np.full((max_batch, self.max_blocks), -1, np.int32)
            self.block_tables: list[BlockTable | None] = [None] * max_batch
            self._step = self._make_step(serving.serve_step_paged)
            self._prefill_sfx = jax.jit(serving.prefill_with_prefix)
            self._prefill_chunk = jax.jit(serving.prefill_chunk_paged,
                                          donate_argnums=(1,))
        else:
            self.cache = self._empty_cache()
            self._step = self._make_step(model.serve_step)
            if self.chunked:
                self._prefill_chunk = jax.jit(model.prefill_chunk_dense,
                                              donate_argnums=(1,))
        # ---- speculative decoding (draft model + multi-token verify)
        self.spec_k = int(spec_k)
        self.speculative = draft_config is not None
        if self.speculative:
            if not self.paged:
                raise ValueError(
                    "speculative decoding needs the paged cache backend "
                    "(the verify pass writes draft K/V through block "
                    "tables); use paged=True")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self.draft_model = Model(draft_config)
            if not self.draft_model.supports_paged:
                raise ValueError(
                    f"{draft_config.name}: the draft model must be "
                    "attention-family (dense-cache decode)")
            if draft_config.vocab != model.cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_config.vocab} != target vocab "
                    f"{model.cfg.vocab}: token-level rejection sampling "
                    "needs a shared vocabulary")
            self.draft_params = (draft_params if draft_params is not None
                                 else self.draft_model.init(
                                     jax.random.PRNGKey(int(draft_seed))))
            # the draft runs a plain dense cache: its KV is tiny, it never
            # shares pages, and stale entries past a rejection are masked
            # by position then overwritten by the next draft chain
            dab = self.draft_model.abstract_cache(max_batch, max_seq)
            self._draft_cache = {
                k: (jnp.full(v.shape, -1, v.dtype) if k == "pos_map"
                    else jnp.zeros(v.shape, v.dtype))
                for k, v in dab.items()}
            self._draft_prefill = jax.jit(self.draft_model.prefill)

            def _dstep(params, cache, batch,
                       _base=self.draft_model.serve_step):
                logits, cache = _base(params, cache, batch)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache

            self._draft_step = jax.jit(_dstep, donate_argnums=(1,))
            self._verify_step = self._make_step(serving.verify_step_paged)
        self.ticks = 0
        self._progress = False
        self.finished: list[Request] = []
        # engine-assigned uids for ContinuumRequest submissions (cluster
        # submissions carry their own positive uids; legacy sync-execute
        # requests use small negatives — this range collides with neither)
        self._auto_uid = 1_000_000_000

    def _make_step(self, base_step):
        """Jit a per-tick decode (or verify) step with the two
        per-tick-overhead fixes: the cache pytree is donated
        (``donate_argnums``) so XLA reuses its buffers instead of
        materializing a full KV-cache copy every tick, and — unless
        ``return_logits`` — the greedy argmax runs on device so only int32
        token ids cross the host link instead of logits over the vocab."""
        if self.return_logits:
            return jax.jit(base_step, donate_argnums=(1,))

        def step_fn(params, cache, batch):
            logits, cache = base_step(params, cache, batch)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        return jax.jit(step_fn, donate_argnums=(1,))

    def page_bytes(self) -> int:
        """Bytes one page pool entry costs across all layers (K+V values
        plus int8 scale rows) — the ``kv_budget_bytes`` unit."""
        cfg = self.model.cfg
        return kv_page_bytes(cfg.n_layers, cfg.n_kv_heads, cfg.hd,
                             self.page_size, self.kv_dtype)

    # ----------------------------------------------------- dense internals
    def _empty_cache(self):
        abstract = self.model.abstract_cache(self.max_batch, self.max_seq)
        return {k: jnp.zeros(v.shape, v.dtype) if k != "pos_map"
                else jnp.full(v.shape, -1, v.dtype)
                for k, v in abstract.items()}

    def _splice(self, slot: int, req_cache: dict, prompt_len: int):
        """Insert a single-request prefill cache into batch slot ``slot``."""
        self.cache = self._splice_cache(self.cache, slot, req_cache)

    @staticmethod
    def _splice_cache(cache: dict, slot: int, req_cache: dict) -> dict:
        """Insert a single-request prefill cache into slot ``slot`` of a
        dense batch cache (the engine's own, or the draft model's)."""
        new = {}
        for name, leaf in cache.items():
            rc = req_cache[name]
            bdim = _BATCH_DIM[name]
            if name in _SEQ_DIM:  # pad request cache S' -> max_seq
                sdim = _SEQ_DIM[name]
                pad = [(0, 0)] * rc.ndim
                pad[sdim] = (0, leaf.shape[sdim] - rc.shape[sdim])
                rc = jnp.pad(rc, pad, constant_values=(
                    -1 if name == "pos_map" else 0))
            idx = [slice(None)] * leaf.ndim
            idx[bdim] = slice(slot, slot + 1)
            new[name] = leaf.at[tuple(idx)].set(rc.astype(leaf.dtype))
        return new

    def _bucket(self, n: int, *, cap: int | None = None) -> int:
        if not self.bucketing:
            return n
        return bucket_length(n, minimum=self.min_bucket,
                             maximum=self.max_seq if cap is None else cap)

    def _padded_prompt(self, toks: np.ndarray, n_pad: int) -> jnp.ndarray:
        out = np.zeros(n_pad, np.int32)
        # clamp: embedding positions carry negative int64 key ids for the
        # prefix trie; the model reads their rows from ``embeds`` instead
        out[:len(toks)] = np.maximum(toks, 0)
        return jnp.asarray(out)[None]

    def _padded_embeds(self, feats: np.ndarray, mask: np.ndarray,
                       n_pad: int):
        """Right-pad a request's embedding rows + mask to the shape bucket
        (zeros / False: padded positions are already masked everywhere)."""
        f = np.zeros((n_pad, feats.shape[1]), np.float32)
        f[:len(feats)] = feats
        m = np.zeros(n_pad, bool)
        m[:len(mask)] = mask
        return jnp.asarray(f)[None], jnp.asarray(m)[None]

    def _with_embeds(self, batch: dict, req: Request, start: int, stop: int,
                     n_pad: int) -> bool:
        """Attach the ``[start, stop)`` slice of a multimodal request's
        embedding rows to a prefill batch; returns whether it did (the
        flag keys the extra XLA trace variant).  A slice with no
        embedding positions — a pure-text chunk past the media span, or a
        suffix whose prefix hit covered the media — stays on the plain
        token trace."""
        if req.features is None or not req.embed_mask[start:stop].any():
            return False
        e, m = self._padded_embeds(req.features[start:stop],
                                   req.embed_mask[start:stop], n_pad)
        batch["embeds"], batch["embed_mask"] = e, m
        return True

    def _note_trace(self, key: tuple):
        """Book a prefill-path shape about to be handed to XLA.  First
        sightings bump the ``xla_trace_events`` counter — the signal the
        steady-state recompile guard gates on (``metrics.reset()`` zeroes
        the counter but never ``self._traced``, matching XLA's persistent
        compile cache)."""
        if key not in self._traced:
            self._traced.add(key)
            self._c_trace_events.inc()

    def _admit_dense(self, slot: int, req: Request):
        """Monolithic (bucketed) prefill into a dense slot; returns the
        first token's next-token logits ``[V]``."""
        req.t_admit = self._now()
        T = len(req.tokens)
        Sb = self._bucket(T)
        batch = {"tokens": self._padded_prompt(req.tokens, Sb),
                 **(req.extra or {})}
        if self.bucketing:
            batch["length"] = jnp.asarray([T], jnp.int32)
        mm = self._with_embeds(batch, req, 0, T, Sb)
        self._note_trace(("prefill", Sb, mm))
        logits, rc = self._prefill(self.params, batch)
        self._splice(slot, rc, T)
        self._c_prefill_computed.inc(T)
        self._c_prefill_padded.inc(Sb - T)
        return logits[0]

    # ----------------------------------------------------- paged internals
    def _cow_page(self, table: BlockTable, blk: int):
        """Make ``table.pages[blk]`` privately writable, copying if shared.
        Every cache leaf is indexed by page id on axis 1 — int8 scale
        tensors included — so the copy moves values and scales together."""
        old = table.pages[blk]
        new, copied = self.pool.ensure_writable(old)
        if copied:
            for name, leaf in self.cache.items():
                self.cache[name] = leaf.at[:, new].set(leaf[:, old])
            self.pool.release(old)
            table.pages[blk] = new

    def _total_blocks(self, req: Request) -> int:
        """Worst-case pages this request can ever hold (prompt + decode;
        speculation adds ``spec_k`` scratch positions so the verify pass
        can always scatter its draft K/V one tick ahead of acceptance)."""
        horizon = len(req.tokens) + req.max_new_tokens
        if self.speculative:
            horizon += self.spec_k
        horizon = min(horizon, self.max_seq)
        return ceil_blocks(horizon, self.page_size)

    def _growth_outstanding(self) -> int:
        """Pages occupied slots may still allocate: decode growth of active
        requests plus the full remaining horizon of mid-chunked-prefill
        slots (their tables hold prompt pages only so far) — admission must
        count both or a promoted request's decode-time ensure_capacity can
        hit an exhausted pool."""
        out = sum(self._total_blocks(r) - len(self.block_tables[i].pages)
                  for i, r in enumerate(self.slots) if r is not None)
        out += sum(self._total_blocks(t.req)
                   - len(self.block_tables[i].pages)
                   for i, t in enumerate(self.prefill_tasks)
                   if t is not None)
        return out

    def _clip_reuse(self, n_reuse: int) -> int:
        """Bound the prefill_with_prefix trace variants on the monolithic
        path: the reused prefix length is a shape dim of that call, so round
        it down to a power-of-two number of pages — O(log max_seq) prefix
        shapes instead of one per distinct hit length.  The chunked path
        has no shape dependence on the reuse length and keeps every token.
        """
        if self.chunked or not self.bucketing or n_reuse <= 0:
            return n_reuse
        blocks = n_reuse // self.page_size
        if blocks == 0:
            return 0
        return (1 << (blocks.bit_length() - 1)) * self.page_size

    def _reserve_table(self, req: Request) -> "tuple[BlockTable, int] | None":
        """Admission control + page reservation for a paged request.

        Returns ``(table, n_reuse)`` with the prefix-hit pages retained and
        capacity for the whole prompt allocated, or None (request must wait)
        when the pool cannot cover this request's worst case on top of every
        active slot's remaining decode growth — so mid-stream page
        allocation can never fail.  Uses the side-effect-free peek first so
        queued retries don't inflate hit stats or churn the LRU.  ``need``
        counts every page this admission removes from the allocatable
        supply: fresh allocations, plus hit pages currently parked in the
        LRU (retaining those shrinks ``num_free`` even though they need no
        allocation), plus the copy-on-write page of a fully-cached prompt.
        """
        toks = np.asarray(req.tokens, np.int64)
        T = len(toks)
        bs = self.page_size
        hit_pages = self.pool.peek_prefix(toks) if self.prefix_caching \
            else []
        est = self._clip_reuse(min(len(hit_pages) * bs, T - 1))
        used = hit_pages[:ceil_blocks(est, bs)] if est else []
        need = self._total_blocks(req) - len(used)
        need += sum(1 for p in used if self.pool.ref[p] == 0)
        if est and est % bs:
            need += 1  # fully-cached prompt: copy-on-write of the last page
        if self.pool.num_free() - self._growth_outstanding() < need:
            return None
        table = BlockTable(self.pool)
        n_reuse = 0
        if self.prefix_caching:
            table.pages, n_hit = self.pool.lookup_prefix(toks)
            # a fully-cached prompt still needs its last token recomputed
            # for the next-token logits -> copy-on-write on the final page
            n_reuse = self._clip_reuse(min(n_hit, T - 1))
            keep = ceil_blocks(n_reuse, bs)
            for p in table.pages[keep:]:  # rounded-off / unused hit pages
                self.pool.release(p)
            table.pages = table.pages[:keep]
        try:
            first_blk = n_reuse // bs
            if n_reuse and first_blk < len(table.pages):
                self._cow_page(table, first_blk)
            table.ensure_capacity(T)
        except OutOfPagesError:  # admission control should prevent this
            table.free()
            return None
        return table, n_reuse

    def _scatter_kv(self, table: BlockTable, positions: np.ndarray, sk, sv,
                    n: int):
        """Scatter ``n`` computed K/V columns ([L, 1, >=n, Hkv, Dh]) into
        the request's pages at the given logical positions.  The int8
        pool is write-then-quantize: monolithic prefill computes exact
        bf16 K/V, rows are quantized here and their scales scattered at
        the same (page, offset) indices."""
        pages, offs = table.rows_for(positions)
        if self.kv_dtype == "int8":
            for vname, sname, leaves in (("k_pages", "k_scales", sk),
                                         ("v_pages", "v_scales", sv)):
                rows, scales = quantize_kv(leaves[:, 0, :n])  # [L,n,Hkv,*]
                self.cache[vname] = \
                    self.cache[vname].at[:, pages, offs].set(rows)
                self.cache[sname] = \
                    self.cache[sname].at[:, pages, offs].set(scales)
            return
        for name, leaves in (("k_pages", sk), ("v_pages", sv)):
            leaf = self.cache[name]
            self.cache[name] = leaf.at[:, pages, offs].set(
                leaves[:, 0, :n].astype(leaf.dtype))

    def _admit_paged(self, slot: int, req: Request):
        """Monolithic (bucketed) paged prefill; returns the first token's
        next-token logits ``[V]``, or None when the pool cannot admit the
        request yet."""
        reserved = self._reserve_table(req)
        if reserved is None:
            return None
        req.t_admit = self._now()
        table, n_reuse = reserved
        toks = np.asarray(req.tokens, np.int64)
        T = len(toks)
        n_sfx = T - n_reuse
        Sb = self._bucket(n_sfx)
        if n_reuse == 0:
            batch = {"tokens": self._padded_prompt(toks, Sb),
                     **(req.extra or {})}
            if self.bucketing:
                batch["length"] = jnp.asarray([T], jnp.int32)
            mm = self._with_embeds(batch, req, 0, T, Sb)
            self._note_trace(("prefill", Sb, mm))
            logits, rc = self._prefill(self.params, batch)
            sk, sv = rc["k"], rc["v"]  # [L, 1, Sb, Hkv, Dh]
        else:
            kp, vp = self.cache["k_pages"], self.cache["v_pages"]
            pre = np.asarray(table.pages, np.int32)
            L, _, _, Hkv, Dh = kp.shape
            if self.kv_dtype == "int8":
                # suffix prefill attends the cached prefix dequantized —
                # the same values decode reads through the fused kernels
                kg = dequantize_kv(kp[:, pre], self.cache["k_scales"][:, pre],
                                   dtype=jnp.bfloat16)
                vg = dequantize_kv(vp[:, pre], self.cache["v_scales"][:, pre],
                                   dtype=jnp.bfloat16)
            else:
                kg, vg = kp[:, pre], vp[:, pre]
            pk = kg.reshape(L, -1, Hkv, Dh)[:, :n_reuse][:, None]
            pv = vg.reshape(L, -1, Hkv, Dh)[:, :n_reuse][:, None]
            batch = {"tokens": self._padded_prompt(toks[n_reuse:], Sb)}
            if self.bucketing:
                batch["length"] = jnp.asarray([n_sfx], jnp.int32)
            mm = self._with_embeds(batch, req, n_reuse, T, Sb)
            self._note_trace(("prefill_sfx", n_reuse, Sb, mm))
            logits, (sk, sv) = self._prefill_sfx(self.params, batch, pk, pv)
        self._scatter_kv(table, np.arange(n_reuse, T), sk, sv, n_sfx)
        if self.prefix_caching:
            self.pool.register_prefix(
                toks, table.pages[:full_blocks(T, self.page_size)])
        self._c_prefill_computed.inc(n_sfx)
        self._c_prefill_padded.inc(Sb - n_sfx)
        self._c_prefix_reused.inc(n_reuse)
        self.block_tables[slot] = table
        self.tables[slot] = table.as_row(self.max_blocks)
        return logits[0]

    def _free_slot(self, slot: int):
        self.slots[slot] = None
        if self.paged:
            self.block_tables[slot].free()
            self.block_tables[slot] = None
            self.tables[slot] = -1
            self.pos[slot] = 0

    # ------------------- KV snapshot export / import (disaggregation)
    def slot_of_request(self, uid: int) -> "int | None":
        """Decode slot currently holding request ``uid``, or None.  A
        request mid-chunked-prefill is *not* found (``slots[slot]`` stays
        None until promotion), so a hit means the request is exportable."""
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                return i
        return None

    def export_kv(self, uid: int) -> KVSnapshot:
        """Checkpoint a decoding request's KV state as a portable
        ``KVSnapshot`` (host-side copy; the request keeps running here).

        The snapshot covers every cache position written so far — the
        prompt plus the generated tokens already fed back through the
        model, i.e. positions ``[0, pos)`` — and records the prompt's
        prefix-trie chain hashes so the importer can re-register (or
        dedupe against) the receiving pool's trie.  Page refcounts are
        held across the device->host copy, so a concurrent eviction on
        this engine cannot recycle a page mid-export."""
        if not self.paged:
            raise ValueError("export_kv needs the paged cache backend")
        slot = self.slot_of_request(uid)
        if slot is None:
            raise ValueError(
                f"request {uid} is not in decode phase on this engine "
                "(queued, mid-prefill, or finished)")
        req = self.slots[slot]
        bs = self.page_size
        n_ctx = int(self.pos[slot])
        pages = list(self.block_tables[slot].pages[:ceil_blocks(n_ctx, bs)])
        for p in pages:
            self.pool.retain(p)
        try:
            leaves = self.model.export_paged_kv(self.cache, pages)
        finally:
            for p in pages:
                self.pool.release(p)
        toks = np.asarray(req.tokens, np.int64)
        n_out = n_ctx - len(toks)
        tokens = np.concatenate(
            [toks, np.asarray(req.output[:n_out], np.int64)])
        snap = KVSnapshot(tokens=tokens, n_prompt=len(toks), block_size=bs,
                          kv_dtype=self.kv_dtype,
                          geometry=self.model.kv_geometry, leaves=leaves,
                          prefix_hashes=BlockPool.chain_hashes(toks, bs),
                          src_pages=pages)
        self._c_kv_exported_pages.inc(len(pages))
        self._c_kv_export_bytes.inc(len(pages) * self.page_bytes())
        return snap

    def evacuate(self, uid: int) -> "tuple[Request, KVSnapshot]":
        """Checkpoint a decoding request and remove it from this engine,
        freeing its slot and pages.  The returned ``Request`` carries the
        snapshot in ``req.imported`` and can be submitted to another
        (KV-compatible) engine, which resumes decode at exactly
        ``output[-1]`` — no tokens are lost or recomputed.  The request
        is *not* added to ``finished``; the caller owns it."""
        snap = self.export_kv(uid)
        slot = self.slot_of_request(uid)
        req = self.slots[slot]
        req.imported = snap
        self._release_group(req)  # it will not finish on this engine
        self._free_slot(slot)
        return req, snap

    def _admit_imported(self, slot: int, req: Request) -> bool:
        """Admit a snapshot-carrying request straight into decode phase:
        adopt its pages into this pool (prefix-trie hits satisfied from
        local cache, the rest imported and converted to this engine's
        ``kv_dtype``) and install the slot at the snapshot's position —
        no prefill pass.  False => pool cannot cover it yet (caller
        requeues).

        CoW safety: decode writes land at logical block
        ``pos // page_size`` with ``pos >= num_tokens >= n_prompt``, i.e.
        strictly past every block this method registers in the trie — so
        adopted/registered pages are never written and need no
        copy-on-write here."""
        snap = req.imported
        n_ctx = snap.num_tokens
        nb = snap.num_pages
        hits = (self.pool.peek_hashes(snap.prefix_hashes)
                if self.prefix_caching else [])
        need = self._total_blocks(req) - len(hits)
        need += sum(1 for p in hits if self.pool.ref[p] == 0)
        if self.pool.num_free() - self._growth_outstanding() < need:
            return False
        table = BlockTable(self.pool)
        if self.prefix_caching:
            table.pages = self.pool.lookup_hashes(snap.prefix_hashes)
        n_hit = len(table.pages)
        try:
            table.ensure_capacity(n_ctx)
        except OutOfPagesError:  # admission control should prevent this
            table.free()
            return False
        if n_hit < nb:
            self.cache = self.model.import_paged_kv(
                self.cache, table.pages[n_hit:nb], snap.leaves,
                snap.kv_dtype, from_block=n_hit)
        if self.prefix_caching:
            self.pool.register_blocks(
                snap.prefix_hashes, table.pages[:len(snap.prefix_hashes)])
        self.block_tables[slot] = table
        self.tables[slot] = table.as_row(self.max_blocks)
        self.slots[slot] = req
        self.pos[slot] = n_ctx
        self.budget[slot] = req.max_new_tokens - len(req.output)
        req.t_admit = self._now()
        self._c_kv_imported_pages.inc(nb - n_hit)
        self._c_kv_import_bytes.inc((nb - n_hit) * self.page_bytes())
        self._c_prefix_reused.inc(n_hit * self.page_size)
        if self.speculative:
            # the snapshot carries no draft-model state: rebuild it by
            # draft-prefilling the context (prompt + emitted tokens)
            self._draft_install(slot, snap.tokens)
        self._progress = True
        return True

    # -------------------------------------------------- chunked prefill
    def _start_prefill(self, slot: int, req: Request) -> bool:
        """Begin a chunked prefill in ``slot``; False => requeued (paged
        pool cannot cover the request yet)."""
        if req.imported is not None:
            if not self._admit_imported(slot, req):
                self.queue.appendleft(req)
                return False
            return True
        if self.paged:
            reserved = self._reserve_table(req)
            if reserved is None:
                self.queue.appendleft(req)
                return False
            table, n_reuse = reserved
            self.block_tables[slot] = table
            self.tables[slot] = table.as_row(self.max_blocks)
            self._c_prefix_reused.inc(n_reuse)
        else:
            n_reuse = 0
            # chunk writes no longer overwrite the whole slot region, so
            # stale pos_map entries from the previous occupant must be
            # cleared up front (stale K/V is then masked everywhere)
            self.cache["pos_map"] = self.cache["pos_map"].at[slot].set(-1)
        req.t_admit = self._now()
        self.prefill_tasks[slot] = _PrefillTask(req, done=n_reuse,
                                                reused=n_reuse)
        return True

    def _advance_prefill(self, slot: int) -> int:
        """Run the next chunk of the slot's in-flight prefill; returns the
        number of token positions computed (charged against the tick's
        prefill budget)."""
        task = self.prefill_tasks[slot]
        req = task.req
        toks = np.asarray(req.tokens, np.int64)
        T = len(toks)
        n = min(self.prefill_chunk, T - task.done)
        Cb = self._bucket(n, cap=self.prefill_chunk)
        batch = {"tokens": self._padded_prompt(toks[task.done:task.done + n],
                                               Cb),
                 "pos": jnp.asarray(task.done, jnp.int32),
                 "length": jnp.asarray(n, jnp.int32)}
        if self.paged:
            batch["block_tables"] = jnp.asarray(self.tables[slot][None])
        else:
            batch["slot"] = jnp.asarray(slot, jnp.int32)
        mm = self._with_embeds(batch, req, task.done, task.done + n, Cb)
        self._note_trace(("prefill_chunk", Cb, mm))
        t0 = self._now() if self._tr is not None else 0.0
        task.logits, self.cache = self._prefill_chunk(
            self.params, self.cache, batch)
        if self._tr is not None:
            self._tr.span("prefill_chunk", "prefill", t0, self._now(),
                          pid=self._pid, tid=req.uid,
                          args={"tokens": n, "done": task.done + n,
                                "total": T})
        task.done += n
        self._c_prefill_computed.inc(n)
        self._c_prefill_padded.inc(Cb - n)
        if self.paged and self.prefix_caching:
            # publish fully-written prompt blocks as they complete, so a
            # request admitted later this tick already hits them
            self.pool.register_prefix(
                toks[:task.done],
                self.block_tables[slot].pages[
                    :full_blocks(task.done, self.page_size)])
        if task.done >= T:  # prompt complete: promote to decoding
            self.prefill_tasks[slot] = None
            self._activate(slot, req, task.logits[0])
        return Cb

    def _schedule_prefill(self):
        """Spend this tick's prefill token budget: advance in-flight chunked
        prefills and admit queued requests into free slots, oldest first.
        Decode steps for already-running slots happen in the same tick, so
        a long prompt can no longer stall them for its whole prefill."""
        budget = self.prefill_budget
        blocked = False  # paged admission failed this tick: stop admitting
        while budget > 0:
            progressed = False
            # admit at most one request per round, then advance every
            # in-flight prefill: a short prompt admitted behind a finished
            # one sees its freshly registered prefix blocks (the admission
            # lookup runs after the earlier prompt's chunks completed)
            if (not blocked and self.queue
                    and (self._admit_quota is None or self._admit_quota > 0)):
                free = next((i for i in range(self.max_batch)
                             if self.slots[i] is None
                             and self.prefill_tasks[i] is None), None)
                if free is not None:
                    req = self.queue.popleft()
                    if self._start_prefill(free, req):
                        progressed = True
                        self._tag_group(req)
                        if self._admit_quota is not None:
                            self._admit_quota -= 1
                    else:
                        blocked = True
            for slot in range(self.max_batch):
                if budget <= 0:
                    break
                if self.prefill_tasks[slot] is None:
                    continue
                budget -= self._advance_prefill(slot)
                progressed = True
            self._progress |= progressed
            if not progressed:
                break
        spent = self.prefill_budget - budget
        if spent and self.telemetry is not None:
            self._h_budget_util.observe(spent / self.prefill_budget)

    # ------------------------------------------- streaming + batched admission
    def _emit_stream(self, req: Request, tok: int, t: float, final: bool):
        """Deliver the token just appended to ``req.output``: a
        ``first_token`` trace instant for the TTFT token, and — when the
        request streams — one ``StreamEvent`` to its callback, as the
        token is decoded rather than at drain."""
        idx = len(req.output) - 1
        if idx == 0 and self._tr is not None:
            self._tr.instant("first_token", "lifecycle", t,
                             pid=self._pid, tid=req.uid)
        if req.stream is None:
            return
        self._c_stream_tokens.inc()
        req.stream(StreamEvent(uid=req.uid, index=idx, token=tok, t_emit=t,
                               first=idx == 0, final=final))

    def _compute_admit_quota(self) -> "int | None":
        """Queued requests that may start prefill this tick under the
        saxml batching knobs (None = unlimited, legacy admission).  Sets
        ``_admission_held`` when the knobs — not resource pressure — are
        what is holding the queue back."""
        self._admission_held = False
        if self.sorted_batch_sizes is None:
            return None
        if not self.queue:
            return 0
        if (self.max_live_batches is not None
                and len(self._group_left) >= self.max_live_batches):
            self._admission_held = True
            return 0
        n = len(self.queue)
        full = max((b for b in self.sorted_batch_sizes if b <= n), default=0)
        if full:
            return full  # fill the largest bucket the queue can cover
        # partial group: released only once the oldest queued request has
        # waited out batching_wait_secs on the engine clock; its bucket is
        # the smallest allowed size >= n, so no group exceeds its bucket
        if (self._now() - self.queue[0].t_submit
                >= self.batching_wait_secs - 1e-12):
            return n
        self._admission_held = True
        return 0

    def _tag_group(self, req: Request):
        """Book a just-admitted request into this tick's admission group
        (live-batch accounting for ``max_live_batches``)."""
        if self.sorted_batch_sizes is None:
            return
        if self._cur_group is None:
            self._cur_group = self._next_group
            self._next_group += 1
            self._group_left[self._cur_group] = 0
            self._cur_size = 0
        req.group = self._cur_group
        self._group_left[self._cur_group] += 1
        self._cur_size += 1

    def _close_admit_group(self):
        if self._cur_group is not None:
            self._h_admit_size.observe(self._cur_size)
            self._cur_group = None

    # ------------------------------------------------------------- public
    def busy(self) -> bool:
        """Any work left: queued, mid-chunked-prefill, or decoding.  The
        single source of idle truth for drain loops and external drivers
        (continuum harness) alike."""
        return bool(self.queue or any(s is not None for s in self.slots)
                    or any(t is not None for t in self.prefill_tasks))

    def make_request(self, creq: ContinuumRequest,
                     uid: "int | None" = None) -> Request:
        """Materialize a typed ``ContinuumRequest`` as this engine's
        internal ``Request`` (uid engine-assigned unless given; a bool
        ``stream`` marker is a cluster-level buffering directive and
        resolves to None here)."""
        if uid is None:
            self._auto_uid += 1
            uid = self._auto_uid
        tokens = (None if creq.tokens is None
                  else np.asarray(creq.tokens, np.int32))
        return Request(uid, tokens, max_new_tokens=int(creq.max_new_tokens),
                       extra=creq.extra, segments=creq.segments,
                       stream=creq.stream if callable(creq.stream) else None)

    def submit(self, req: "Request | ContinuumRequest") -> Request:
        """Queue a request; accepts the internal ``Request`` or the typed
        ``ContinuumRequest`` (converted via ``make_request``).  Returns
        the queued internal request."""
        if isinstance(req, ContinuumRequest):
            req = self.make_request(req)
        if req.tokens is None:
            raise ValueError(f"request {req.uid}: no tokens or segments")
        if req.features is not None:
            if not self.model.supports_embed_spans:
                raise ValueError(
                    f"request {req.uid}: embedding-span prompts need an "
                    f"attention-family model, not {self.model.cfg.name}")
            if req.features.shape[1] != self.model.cfg.d_model:
                raise ValueError(
                    f"request {req.uid}: segment features of dim "
                    f"{req.features.shape[1]} do not match the model's "
                    f"d_model={self.model.cfg.d_model}")
        if len(req.tokens) > self.max_seq - 1:
            raise ValueError(
                f"request {req.uid}: prompt of {len(req.tokens)} tokens "
                f"exceeds the engine's capacity — max_seq={self.max_seq} "
                f"leaves room for at most {self.max_seq - 1} prompt tokens "
                "plus one generated token; raise max_seq or truncate the "
                "prompt")
        if len(req.tokens) < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.imported is not None:
            snap = req.imported
            if not self.paged:
                raise ValueError(
                    f"request {req.uid}: KV snapshot import needs the "
                    "paged cache backend")
            if snap.geometry != self.model.kv_geometry:
                raise ValueError(
                    f"request {req.uid}: snapshot KV geometry "
                    f"{snap.geometry} does not match this engine's "
                    f"{self.model.kv_geometry}")
            if snap.block_size != self.page_size:
                raise ValueError(
                    f"request {req.uid}: snapshot block_size "
                    f"{snap.block_size} != engine page_size "
                    f"{self.page_size}")
            if snap.num_tokens > self.max_seq - 1:
                raise ValueError(
                    f"request {req.uid}: snapshot of {snap.num_tokens} "
                    f"tokens exceeds max_seq={self.max_seq} - 1")
            if not req.output or req.done:
                raise ValueError(
                    f"request {req.uid}: a snapshot-carrying request must "
                    "be mid-decode (non-empty output, not done)")
        # a migrated request keeps its original submit stamp so queue-time
        # and e2e span the source engine too (shared virtual-clock base)
        if not req.token_times:
            req.t_submit = self._now()
        self._c_submitted.inc()
        if self._tr is not None:
            self._tr.instant("submit", "lifecycle", req.t_submit,
                             pid=self._pid, tid=req.uid)
        self.queue.append(req)
        return req

    def _finish(self, req: Request):
        """Request complete: move to ``finished``, fold its latencies into
        the registry histograms (so ``latency_stats`` survives drain loops
        popping ``self.finished``), and emit its lifecycle spans."""
        req.done = True
        self.finished.append(req)
        self._c_finished.inc()
        self._release_group(req)
        tt = req.token_times
        imported = req.imported is not None
        ta = req.t_admit if req.t_admit >= req.t_submit else req.t_submit
        if not imported:
            # a migrated request's queue/prefill phases ran on the source
            # engine (its t_admit here postdates tt[0]); only the decode
            # span and the end-to-end latencies are meaningful locally
            self._h_queue.observe(ta - req.t_submit)
        self._h_ttft.observe(tt[0] - req.t_submit)
        self._h_e2e.observe(tt[-1] - req.t_submit)
        if len(tt) > 1:
            self._h_itl.extend(b - a for a, b in zip(tt, tt[1:]))
        tr = self._tr
        if tr is not None:
            pid, tid = self._pid, req.uid
            if not imported:
                tr.span("queue", "lifecycle", req.t_submit, ta,
                        pid=pid, tid=tid)
                tr.span("prefill", "lifecycle", ta, tt[0], pid=pid, tid=tid,
                        args={"prompt_tokens": len(req.tokens)})
            tr.span("decode", "lifecycle", tt[0], tt[-1], pid=pid, tid=tid,
                    args={"new_tokens": len(req.output)})

    def _release_group(self, req: Request):
        """Retire a request from its admission group; a fully-retired
        group frees a ``max_live_batches`` slot."""
        if req.group is None:
            return
        left = self._group_left.get(req.group, 1) - 1
        if left <= 0:
            self._group_left.pop(req.group, None)
        else:
            self._group_left[req.group] = left
        req.group = None

    def _activate(self, slot: int, req: Request, logits):
        """Install an admitted request into its decode slot, given its
        prompt's next-token ``logits`` [V], honoring EOS and the generation
        budget at admission: a request whose first prefill-sampled token
        already ends it (eos, or max_new_tokens == 1) finishes immediately
        instead of decoding its full budget."""
        first_tok = int(jnp.argmax(logits))
        if self.return_logits:
            req.logits.append(np.asarray(logits, np.float32))
        req.output.append(first_tok)
        req.token_times.append(self._now())
        ends = (req.max_new_tokens <= 1
                or (self.eos_id is not None and first_tok == self.eos_id))
        self._emit_stream(req, first_tok, req.token_times[-1], ends)
        if ends:
            self._finish(req)
            if self.paged and self.block_tables[slot] is not None:
                self.block_tables[slot].free()
                self.block_tables[slot] = None
                self.tables[slot] = -1
            return
        self.slots[slot] = req
        self.pos[slot] = len(req.tokens)
        self.budget[slot] = req.max_new_tokens - 1
        if self.speculative:
            self._draft_install(slot, req.tokens)

    def _draft_install(self, slot: int, tokens):
        """(Re)build the draft model's dense-cache state for ``slot`` by
        prefilling ``tokens`` (the prompt — or, for an imported snapshot,
        prompt + already-emitted output) with the draft weights.  Media
        key ids are clamped to token 0, so draft quality may drop over
        embedding spans; verification makes the emitted stream
        independent of draft quality either way."""
        toks = np.asarray(tokens, np.int64)
        T = len(toks)
        Sb = self._bucket(T)
        batch = {"tokens": self._padded_prompt(toks, Sb)}
        if self.bucketing:
            batch["length"] = jnp.asarray([T], jnp.int32)
        self._note_trace(("draft_prefill", Sb))
        _, rc = self._draft_prefill(self.draft_params, batch)
        self._draft_cache = self._splice_cache(self._draft_cache, slot, rc)

    def acceptance_rate(self, default: float = 0.6) -> float:
        """Live draft-token acceptance rate (accepted / drafted) since the
        last ``metrics.reset()``; ``default`` until any tokens have been
        drafted.  The router's speculative-shape pricing reads this."""
        drafted = self._c_spec_drafted.value
        if drafted <= 0:
            return float(default)
        return self._c_spec_accepted.value / drafted

    def _admit(self):
        """Monolithic admission path (chunking disabled, or recurrent/
        hybrid families whose state cannot be chunk-prefilled)."""
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.queue:
                continue
            if self._admit_quota is not None and self._admit_quota <= 0:
                break  # this tick's admission group is full
            req = self.queue.popleft()
            if req.imported is not None:
                if self._admit_imported(slot, req):
                    self._tag_group(req)
                    if self._admit_quota is not None:
                        self._admit_quota -= 1
                    continue
                self.queue.appendleft(req)
                break  # out of pages: wait for running requests to finish
            admit = self._admit_paged if self.paged else self._admit_dense
            logits = admit(slot, req)
            if logits is None:
                self.queue.appendleft(req)
                break  # out of pages: wait for running requests to finish
            self._progress = True
            self._tag_group(req)
            if self._admit_quota is not None:
                self._admit_quota -= 1
            self._activate(slot, req, logits)

    def step(self) -> int:
        """One engine tick: spend the prefill budget (chunked path) or
        admit monolithically, then one batched decode step for every
        fully-prefilled slot.  Returns the number of occupied slots.

        **Single-tick contract** (external drivers — e.g. the continuum
        harness — rely on this): one call performs at most one batched
        decode step, is safe to call with no work pending (it is then a
        cheap no-op returning 0), and only mutates ``self.ticks`` by one
        when any slot is occupied or prefilling.  An external scheduler may
        therefore interleave ``step()`` calls across several engines under
        a shared virtual clock; ``run_until_drained`` is just a loop over
        this method with a *relative* ``drain_deadline`` guard, so the two
        driving styles compose (draining never depends on the global tick
        count accumulated by earlier external stepping)."""
        self._progress = False  # any admission/prefill advance this tick
        self._admit_quota = self._compute_admit_quota()
        if self.chunked:
            self._schedule_prefill()
        else:
            self._admit()
        self._close_admit_group()
        self._g_queue_depth.set(len(self.queue))
        active = [i for i, r in enumerate(self.slots) if r is not None]
        n_prefilling = sum(t is not None for t in self.prefill_tasks)
        if self._tr is not None and (active or n_prefilling or self.queue):
            self._sample_tick(len(active), n_prefilling)
        if not active:
            if n_prefilling:
                self.ticks += 1
            return n_prefilling
        if self.speculative:
            self._spec_tick(active)
            self.ticks += 1
            return len(active) + n_prefilling
        tokens = np.zeros(self.max_batch, np.int32)
        # slots without a decodable request (free, or still prefilling) are
        # masked out of the decode step: dense writes land at the
        # out-of-bounds position max_seq (XLA drops them), paged rows get a
        # null block table, so a mid-prefill slot's cache is never touched
        pos = np.full(self.max_batch, self.max_seq, np.int64)
        for i in active:
            tokens[i] = self.slots[i].output[-1]
            pos[i] = self.pos[i]
        batch = {"tokens": jnp.asarray(tokens),
                 "pos": jnp.asarray(pos, jnp.int32)}
        if self.paged:
            for i in active:  # grow block tables across page boundaries
                bt = self.block_tables[i]
                if self.pos[i] >= bt.num_tokens_capacity():
                    bt.ensure_capacity(self.pos[i] + 1)
                    self.tables[i] = bt.as_row(self.max_blocks)
            tables = np.full_like(self.tables, -1)
            for i in active:
                tables[i] = self.tables[i]
            pos[pos >= self.max_seq] = 0  # clamp masked rows (null table)
            batch["pos"] = jnp.asarray(pos, jnp.int32)
            batch["block_tables"] = jnp.asarray(tables)
        t0 = self._now() if self._tr is not None else 0.0
        out, self.cache = self._step(self.params, self.cache, batch)
        # default path: ``out`` is already the [B] argmax token ids,
        # computed on device — one int32 per slot crosses the host link
        out = np.asarray(out)
        nxt = out.argmax(-1) if self.return_logits else out
        self.ticks += 1
        self._c_decode_tokens.inc(len(active))
        t_now = self._now()
        if self._tr is not None:
            self._tr.span("decode_tick", "engine", t0, t_now, pid=self._pid,
                          args={"active": len(active)})
        for i in active:
            req = self.slots[i]
            tok = int(nxt[i])
            req.output.append(tok)
            if self.return_logits:
                req.logits.append(out[i])
            req.token_times.append(t_now)
            self.pos[i] += 1
            self.budget[i] -= 1
            ends = bool(self.budget[i] <= 0 or tok == self.eos_id
                        or self.pos[i] >= self.max_seq - 1)
            self._emit_stream(req, tok, t_now, ends)
            if ends:
                self._finish(req)
                self._free_slot(i)  # free slot/pages (continuous batching)
        return len(active) + n_prefilling

    def _spec_tick(self, active: "list[int]"):
        """One speculative decode tick: the draft model proposes ``spec_k``
        tokens per active slot (``spec_k`` cheap dense decode steps), the
        target model scores the last accepted token plus all drafts in one
        multi-token verify pass, and each slot emits the longest agreeing
        prefix plus the target's correction token — 1 to ``spec_k + 1``
        tokens, bit-identical to plain greedy decode.

        Rejected drafts leave stale K/V at positions past the new ``pos``
        in both caches; every read masks ``cache_pos <= query_pos`` and the
        next tick's writes overwrite them in order, so rollback costs
        nothing.  Stream events are emitted per token with contiguous
        indices and timestamps interpolated across the tick (monotone
        non-decreasing), and ``final`` only on the true last token."""
        k = self.spec_k
        B = self.max_batch
        t0 = self._now()
        # masked slots (free / mid-prefill): pos = max_seq puts every dense
        # draft write out of bounds (dropped) and, with a null block table,
        # every verify write/read on an invalid page (dropped/masked)
        cur = np.zeros(B, np.int32)
        base = np.full(B, self.max_seq, np.int64)
        for i in active:
            cur[i] = self.slots[i].output[-1]
            base[i] = self.pos[i]
        drafts = np.zeros((B, k), np.int32)
        for t in range(k):
            dpos = np.minimum(base + t, self.max_seq)
            ids, self._draft_cache = self._draft_step(
                self.draft_params, self._draft_cache,
                {"tokens": jnp.asarray(cur),
                 "pos": jnp.asarray(dpos, jnp.int32)})
            cur = np.asarray(ids)
            drafts[:, t] = cur
        t_draft = self._now() if self._tr is not None else t0
        # grow block tables to cover the k+1 verify positions; admission
        # reserved spec_k slack in _total_blocks, so this cannot exhaust
        # the pool (positions clamped at max_seq simply drop their writes)
        for i in active:
            bt = self.block_tables[i]
            cap = min(int(base[i]) + k + 1, self.max_seq)
            if cap > bt.num_tokens_capacity():
                bt.ensure_capacity(cap)
                self.tables[i] = bt.as_row(self.max_blocks)
        vt = np.zeros((B, k + 1), np.int32)
        for i in active:
            vt[i, 0] = self.slots[i].output[-1]
            vt[i, 1:] = drafts[i]
        tables = np.full_like(self.tables, -1)
        for i in active:
            tables[i] = self.tables[i]
        out, self.cache = self._verify_step(
            self.params, self.cache,
            {"tokens": jnp.asarray(vt),
             "pos": jnp.asarray(np.minimum(base, self.max_seq), jnp.int32),
             "block_tables": jnp.asarray(tables)})
        out = np.asarray(out)
        # [B, k+1] target argmax per verify position
        ids = out.argmax(-1) if self.return_logits else out
        t_now = self._now()
        if self._tr is not None:
            self._tr.span("draft_tick", "engine", t0, t_draft,
                          pid=self._pid, args={"active": len(active),
                                               "k": k})
            self._tr.span("verify_tick", "engine", t_draft, t_now,
                          pid=self._pid, args={"active": len(active),
                                               "k": k})
        n_tok = tick_acc = 0
        for i in active:
            req = self.slots[i]
            # ids[i, j] is the target's token after consuming vt[i, :j+1];
            # draft j (= vt[i, j+1]) is accepted iff it equals ids[i, j]
            n_acc = 0
            while n_acc < k and drafts[i, n_acc] == ids[i, n_acc]:
                n_acc += 1
            emit = [int(x) for x in ids[i, :n_acc + 1]]
            n_emit = len(emit)
            emitted = 0
            for tok in emit:
                if self.return_logits:
                    req.logits.append(out[i, emitted])
                emitted += 1
                req.output.append(tok)
                ts = t0 + (t_now - t0) * emitted / n_emit
                req.token_times.append(ts)
                self.pos[i] += 1
                self.budget[i] -= 1
                ends = bool(self.budget[i] <= 0 or tok == self.eos_id
                            or self.pos[i] >= self.max_seq - 1)
                self._emit_stream(req, tok, ts, ends)
                if ends:
                    self._finish(req)
                    self._free_slot(i)
                    break
            # drafts consumed into the stream; accepted-but-unemitted
            # drafts past an eos/budget stop count as wasted
            acc = emitted - 1
            self._c_spec_drafted.inc(k)
            self._c_spec_accepted.inc(acc)
            self._c_spec_wasted.inc(k - acc)
            n_tok += emitted
            tick_acc += acc
        self._c_decode_tokens.inc(n_tok)
        drafted = self._c_spec_drafted.value
        if drafted:
            self._g_accept_rate.set(self._c_spec_accepted.value / drafted)
        if self._tr is not None:
            self._tr.counter("spec_tokens", t_now,
                             {"drafted": k * len(active),
                              "accepted": tick_acc,
                              "emitted": n_tok}, pid=self._pid)

    def _sample_tick(self, n_active: int, n_prefilling: int):
        """Per-tick occupancy counter samples (tracing enabled only)."""
        tr, now = self._tr, self._now()
        tr.counter("batch_occupancy", now,
                   {"decoding": n_active, "prefilling": n_prefilling},
                   pid=self._pid)
        tr.counter("queue_depth", now,
                   {"queued": len(self.queue),
                    "live_batches": len(self._group_left)}, pid=self._pid)
        if self.paged:
            tr.counter("kv_pages", now,
                       {"in_use": self.pool.pages_in_use(),
                        "cached": len(self.pool.lru)}, pid=self._pid)

    def run_until_drained(self, max_ticks: int = 10_000,
                          keep_finished: bool = False):
        """Step until queue, prefill tasks and slots are all empty.

        Returns the finished requests; ``keep_finished=True`` leaves them
        on ``self.finished`` too (so ``latency_stats`` still sees them).

        ``max_ticks`` bounds the ticks spent *inside this call* (a
        ``drain_deadline`` relative to the current ``self.ticks``), so an
        engine that has already been stepped externally for a long run —
        the continuum harness advances engines tick-by-tick — can still be
        drained afterwards.  The guard used to compare against the global
        tick counter and tripped immediately in that case.
        """
        drain_deadline = self.ticks + max_ticks
        spins = 0  # ticks spent holding admission (batching knobs)
        while self.busy():
            if self.step() == 0 and self.queue and not self._progress:
                if self._admission_held:
                    # the batching knobs — not resource pressure — are
                    # holding the queue: with a wall clock the wait simply
                    # elapses; a virtual clock needs an external driver,
                    # so spinning is bounded rather than diagnosed as OOM
                    spins += 1
                    if spins > max(max_ticks, 100_000):
                        raise RuntimeError(
                            "engine did not drain: admission held by the "
                            "batching knobs but the clock never advanced "
                            "(virtual-clock engines must be driven "
                            "externally when batching_wait_secs > 0)")
                    continue
                # nothing active yet admission failed: the head request can
                # never fit (its worst case exceeds the whole pool)
                head = self.queue[0]
                raise OutOfPagesError(
                    f"request {head.uid} needs {self._total_blocks(head)} "
                    f"pages but the pool only has {self.pool.num_pages - 1}")
            if self.ticks > drain_deadline:
                raise RuntimeError("engine did not drain")
        if keep_finished:
            return list(self.finished)
        out, self.finished = self.finished, []
        return out

    def reset_prefix_cache(self):
        """Drop every parked prefix block (paged path): the next admission
        sees a cold cache.  The continuum replay harness calls this
        between replays so runs are independent and deterministic (a warm
        trie would hand later replays prefix hits the first one paid for).
        K/V pages are only ever read through block tables, so the stale
        device arrays need no zeroing.  Requires an idle engine."""
        if not self.paged:
            return
        if self.busy():
            raise RuntimeError("reset_prefix_cache needs an idle engine")
        self.pool = BlockPool(self.pool.num_pages, self.page_size)

    # -------------------------------------------------------------- stats
    # back-compat: these were plain attributes before the registry existed
    @property
    def prefill_tokens_computed(self) -> int:
        return self._c_prefill_computed.value

    @property
    def prefill_tokens_padded(self) -> int:
        return self._c_prefill_padded.value

    @property
    def prefix_tokens_reused(self) -> int:
        return self._c_prefix_reused.value

    def kv_cache_bytes(self) -> int:
        """Current KV-cache footprint (allocated device arrays)."""
        return sum(int(np.prod(v.shape)) * v.dtype.itemsize
                   for v in self.cache.values())

    def prefill_trace_count(self) -> int:
        """Distinct prefill-path shapes handed to XLA so far.  With
        bucketing this is bounded by the bucket count (O(log max_seq));
        without it every distinct prompt length is a fresh compile."""
        return len(self._traced)

    def jit_cache_sizes(self) -> dict:
        """Actual XLA trace counts per jitted entry point (when the jax
        version exposes them) — ground truth for the recompile-storm
        regression test."""
        out = {}
        for name in ("_prefill", "_prefill_sfx", "_prefill_chunk", "_step",
                     "_draft_prefill", "_draft_step", "_verify_step"):
            fn = getattr(self, name, None)
            size = getattr(fn, "_cache_size", None)
            if size is not None:
                out[name] = size()
        return out

    def latency_stats(self) -> dict:
        """TTFT / inter-token / end-to-end latency percentiles (seconds).

        Alias for ``stats()["latency"]`` kept for callers that only want
        the latency block without the full registry snapshot; both are
        thin views over the registry's ``ttft_s``/``itl_s``/``e2e_s``
        histograms, observed as each request finishes (so the numbers
        survive ``run_until_drained`` popping ``self.finished``;
        accumulation is scoped by ``metrics.reset()``, which
        ``Cluster.reset`` calls between replays).  Timestamps come from
        the engine's ``clock``: wall seconds by default, **virtual-clock
        seconds** when an external driver (the continuum harness) steps
        the engine under its own clock."""
        return latency_summary(self._h_ttft.values, self._h_itl.values,
                               self._h_e2e.values)

    def stats(self) -> dict:
        """The one-stop engine accessor: static configuration, a full
        metrics-registry snapshot (counters as ints, histograms as
        summary dicts, pool/trace views evaluated live), and the latency
        percentiles under ``"latency"`` (the ``latency_stats()`` block —
        that method remains as a documented alias)."""
        out = {"paged": self.paged, "kv_dtype": self.kv_dtype,
               "bucketed": self.bucketing, "chunked": self.chunked,
               "speculative": self.speculative,
               "spec_k": self.spec_k if self.speculative else 0,
               "acceptance_rate": (self.acceptance_rate()
                                   if self.speculative else None)}
        out.update(self.metrics.snapshot())
        out["latency"] = self.latency_stats()
        return out
