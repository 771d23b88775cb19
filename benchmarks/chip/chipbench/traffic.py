"""One general traffic generator, driven by a mix's data file.

A mix (``benchmarks/chip/traffic/<name>.json``) gives parameters only:

``loop``            ``"open"`` (arrivals on a schedule) or ``"closed"``
                    (each client sends its next request when the last one
                    finishes).
``arrivals``        open loop: ``{"process": "poisson", "rate_per_s": r}``.
``clients_per_slot`` closed loop: clients per engine slot.
``prefix_tokens``   a system prompt shared by every request of a run.
``media``           ``[{"kind", "rows", "share"}, ...]``: the share of tasks
                    whose prompt carries an embedding span of ``rows`` rows
                    (image patches, audio frames) after the prefix.
``text_tokens``     the length of each task's own text (a distribution).
``asks``            ``{"min", "max", "spread_s"}``: each task is asked a
                    uniform number of times in [min, max]; a later ask is
                    due a uniform 0 to ``spread_s`` seconds after the first
                    and takes the first arrival from then on, with the text
                    rotated by one token per ask (same prefix and media).
``output_tokens``   the generation length of each ask (a distribution).
``first_output``    closed loop: ``"residual"`` draws each client's first
                    request from the length-biased residual of
                    ``output_tokens``, so the batch starts in steady state.
``reorder``         closed loop: the seed reorders each run of this many
                    consecutive requests (a divisor of the number of
                    clients; default 1, no reordering).

A distribution is ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}`` (whole numbers, both
ends included).

Every size and gap is a stratified draw: the same quantiles of the
distribution for every seed, put in an order drawn from the seed.  An open
loop sends a fixed set of asks: round(rate * seconds) of them, each kind's
share of them exact.  A closed loop's n-th request, whichever client
sends it, takes the n-th entry of one sequence of sizes: blocks of one
request a slot, each a stratified set of (prompt, output) pairs, in an
order that is the same for every seed; the seed reorders only each run of
``reorder`` consecutive requests.  A window sees just a few blocks (the
slots' first requests, those queued behind them, the next round), so a
seed that reordered whole blocks would change which sizes run together,
and so the work inside the window.  So two seeds send the same work in a
different order, and the spread between runs measures the system, not the
draw.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy.special import ndtri

# the reference sample behind distributions with no closed-form quantile
_REF_SAMPLE = 200_000


@dataclasses.dataclass
class Item:
    """One request as the client sends it."""
    uid: int
    prefix: np.ndarray  # shared system-prompt token ids
    text: np.ndarray  # the task's own token ids (rotated per ask)
    max_new: int
    media_kind: "str | None" = None
    media: "np.ndarray | None" = None  # [rows, d] float32 embedding rows
    task: int = -1
    ask: int = 0
    due: float = 0.0  # open loop: seconds after the window opens
    client: int = -1  # closed loop

    @property
    def prompt_len(self) -> int:
        n = len(self.prefix) + len(self.text)
        return n + (0 if self.media is None else len(self.media))


def _u(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    """Whole-number quantiles of ``dist`` at probabilities ``u``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * ndtri(u))
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    if dist["dist"] == "uniform":
        return np.clip(lo + np.floor(u * (hi - lo + 1)), lo, hi).astype(
            np.int64)
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def residual_quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    """Quantiles of what is left of a request caught mid-generation: its
    total is length-biased (a long request is more likely to be in
    flight), and what is left is uniform over 1..total."""
    rng = np.random.default_rng(0)  # fixed: the same set for every seed
    total = quantiles(dist, rng.random(_REF_SAMPLE))
    keep = rng.random(_REF_SAMPLE) < total / total.max()
    total = total[keep]
    left = 1 + np.floor(rng.random(len(total)) * total).astype(np.int64)
    return np.quantile(left, u, method="inverted_cdf").astype(np.int64)


def stratified(dist: dict, n: int, rng) -> np.ndarray:
    return rng.permutation(quantiles(dist, _u(n)))


def blocks(values: np.ndarray, width: int, rng) -> np.ndarray:
    """``values`` (a multiple of ``width`` of them) as rows of ``width``,
    column i of every row one value of the i-th of ``width`` strata of
    consecutive ranks, so every row is a stratified set; ``rng`` deals
    each stratum's values to the rows."""
    strata = np.sort(values).reshape(width, -1)
    return np.stack([rng.permutation(row) for row in strata], axis=1)


def _shares(media: list, n: int, rng) -> list:
    """Exactly round(share * n) tasks of each media kind (None: text only),
    in an order drawn from the seed."""
    kinds: list = []
    for m in media:
        kinds += [m] * int(round(m["share"] * n))
    kinds = kinds[:n] + [None] * max(0, n - len(kinds))
    return [kinds[i] for i in rng.permutation(n)]


class Traffic:
    """The requests of one run of one mix: ``items`` in the order they are
    due (open loop), or each client's stream (closed loop)."""

    def __init__(self, mix: dict, *, seed: int, seconds: float, vocab: int,
                 d_model: int, max_batch: int, rate_per_s: "float | None" =
                 None):
        self.mix = mix
        self.loop = mix["loop"]
        self.vocab, self.d_model = vocab, d_model
        self.rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
        self.rows_seed = int(self.rng.integers(2**62))
        self.prefix = self._tokens(int(mix.get("prefix_tokens", 0)))
        self._uid = 0
        if self.loop == "open":
            arr = dict(mix["arrivals"])
            if rate_per_s is not None:
                arr["rate_per_s"] = rate_per_s
            self.rate = float(arr["rate_per_s"])
            self.items = self._open(arr, seconds)
        elif self.loop == "closed":
            self.max_batch = max_batch
            self.clients = int(mix["clients_per_slot"]) * max_batch
            self._closed_setup()
        else:
            raise ValueError(f"unknown loop {self.loop!r}")

    # ------------------------------------------------------------ pieces
    def _tokens(self, n: int, rng=None) -> np.ndarray:
        rng = self.rng if rng is None else rng
        return rng.integers(0, self.vocab, n).astype(np.int32)

    def _rows(self, task: int, rows: int) -> np.ndarray:
        """A task's media rows at the embedding's scale, from the task's
        own seed: every ask of one task carries the same bytes."""
        rng = np.random.default_rng([self.rows_seed, task])
        return (rng.standard_normal((rows, self.d_model), np.float32)
                * np.float32(self.d_model ** -0.5))

    def _item(self, **kw) -> Item:
        it = Item(uid=self._uid, prefix=self.prefix, **kw)
        self._uid += 1
        return it

    # ------------------------------------------------------------ open loop
    def _arrivals(self, arr: dict, seconds: float) -> np.ndarray:
        """round(rate * seconds) arrival times in [0, seconds): exponential
        gaps at stratified quantiles in a seeded order, scaled to fill the
        window (given their count, Poisson arrivals are spread so)."""
        n = max(1, int(round(self.rate * seconds)))
        gaps = self.rng.permutation(-np.log1p(-_u(n + 1)))
        t = np.cumsum(gaps)[:n] / gaps.sum() * seconds
        if arr["process"] == "poisson":
            return t
        raise ValueError(f"unknown arrival process {arr['process']!r}")

    def _open(self, arr: dict, seconds: float) -> "list[Item]":
        """A fixed set of asks, one per arrival: tasks asked min, min + 1,
        ..., max times in turn until the asks fill the arrivals, each
        media kind's share of every ask count exact, text lengths
        stratified within each kind, the tasks in an order drawn from the
        seed."""
        mix, sr = self.mix, self.rng
        asks = mix.get("asks", {"min": 1, "max": 1, "spread_s": 0.0})
        a_lo, a_hi = int(asks["min"]), int(asks["max"])
        spread = float(asks["spread_s"])
        times = self._arrivals(arr, seconds)
        n = len(times)
        counts: list = []
        while sum(counts) < n:
            counts.append(a_lo + len(counts) % (a_hi - a_lo + 1))
        counts[-1] -= sum(counts) - n
        counts = np.asarray(counts)
        kinds: list = [None] * len(counts)
        for c in np.unique(counts):
            idx = np.flatnonzero(counts == c)
            for j, m in zip(idx, _shares(mix.get("media", []), len(idx),
                                         sr)):
                kinds[j] = m
        order = sr.permutation(len(counts))
        counts, kinds = counts[order], [kinds[j] for j in order]
        kind_of = [None if m is None else m["kind"] for m in kinds]
        text_len = np.zeros(len(counts), np.int64)
        for key in sorted(set(kind_of), key=str):
            idx = [j for j, k in enumerate(kind_of) if k == key]
            text_len[idx] = stratified(mix["text_tokens"], len(idx), sr)
        out_len = stratified(mix["output_tokens"], n, sr)
        # each task in turn takes the earliest free arrival for its first
        # ask, and for each later ask the first free one from a uniform 0
        # to ``spread`` seconds after it (or the last free one, near the
        # window's end); every free arrival lies after the first ask
        free = list(range(n))
        taken = []  # (arrival, task, ask)
        for j in range(len(counts)):
            first = free.pop(0)
            taken.append((first, j, 0))
            for k in range(1, int(counts[j])):
                due = times[first] + sr.random() * spread
                at = min(int(np.searchsorted(times[free], due)),
                         len(free) - 1)
                taken.append((free.pop(at), j, k))
        items, tasks = [], {}
        for i, j, k in sorted(taken):
            if j not in tasks:
                m = kinds[j]
                tasks[j] = (self._tokens(int(text_len[j])), kind_of[j],
                            None if m is None else self._rows(j, m["rows"]))
            text, kind, media = tasks[j]
            items.append(self._item(text=np.roll(text, k),
                                    max_new=int(out_len[i]), media_kind=kind,
                                    media=media, task=j, ask=k,
                                    due=float(times[i])))
        return items

    # ---------------------------------------------------------- closed loop
    def _closed_setup(self):
        mix, C = self.mix, self.clients
        rng = self.rng
        self._streams = [np.random.default_rng([self.rows_seed, c])
                         for c in range(C)]
        # sizes: the n-th request sent takes entry n of one sequence
        # (wrapping after C * 16): blocks of one request a slot, prompt
        # stratum i with output stratum pair[i], in a fixed order; the
        # first C entries are the requests sent at set-up
        n, B = C * 16, self.max_batch
        g = int(mix.get("reorder", 1))
        if C % g:
            raise ValueError(f"reorder {g} does not divide {C} clients")
        fixed = np.random.default_rng(0)  # the same sequence for every seed
        pair = fixed.permutation(B)
        prompt = blocks(quantiles(mix["text_tokens"], _u(n)), B, fixed)
        out = blocks(quantiles(mix["output_tokens"], _u(n)), B, fixed)[:, pair]
        if mix.get("first_output") == "residual":
            out[:C // B] = blocks(residual_quantiles(
                mix["output_tokens"], _u(C)), B, fixed)[:, pair]
        base = np.stack([fixed.permutation(B) for _ in range(n // B)])
        order = np.concatenate([k + rng.permutation(g)
                                for k in range(0, n, g)])
        self._prompt = np.take_along_axis(prompt, base, 1).ravel()[order]
        self._out = np.take_along_axis(out, base, 1).ravel()[order]
        self._sent = [0] * C
        self._n = 0

    def next_for(self, client: int) -> Item:
        """The next request, sent by ``client``: its sizes the next entry
        of the run's sequence, its tokens from the client's own stream."""
        k = self._sent[client]
        self._sent[client] += 1
        j = self._n % len(self._prompt)
        self._n += 1
        rng = self._streams[client]
        return self._item(text=self._tokens(int(self._prompt[j]), rng),
                          max_new=int(self._out[j]), client=client, ask=k)
