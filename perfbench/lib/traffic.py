"""The one general traffic generator. A traffic mix is a data file of
parameters (``traffic/<name>.json``); this module turns it and ``--seed``
into inputs, and hands the program nothing else.

Every seed gets the same SET of sizes and arrivals in another order: sizes
and inter-arrival gaps are the quantiles of their distributions over a block
of ``block`` requests (a stratified sample, no chance in it), and the seed
only permutes them within sub-blocks of ``shuffle`` requests and draws the
token ids. So two seeds differ by order over a few requests, not by how much
work a window holds (with whole blocks shuffled, a 50 s window of 45 chat
requests read 125 to 142 tokens/s from seed to seed, and 1% apart on one
seed: my chip runs, PR 23). In a closed loop even the order within a
sub-block of 8 decides which contexts share the batch, and so the rate (182 to
192 tokens/s over three seeds, 0.3% apart on one seed): the serving mixes fix
the order too (``order_seed``), and ``--seed`` draws token ids and weights.

Kinds of mix:
  ``tokens``    training batches: ``[batch, seq_len]`` ids drawn afresh for
                every step from a skewed unigram distribution (so the loss
                has something to learn and falls)
  ``requests``  serving: an open loop (``arrival.mode = "poisson"``, a fixed
                ``rate_per_s``) or a closed loop (``"closed"``, ``clients``
                callers that each wait for their reply); prompts either
                unshared, or ``documents`` asked ``asks`` times each in
                shuffled order with a fresh question tail
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generators from one ``--seed`` (any whole number)."""
    return np.random.default_rng([int(seed), int(stream)])


def length_quantile(spec: Dict[str, Any], u: float) -> int:
    """The ``u``-quantile of a length distribution: log-uniform between
    ``min`` and ``body_max`` for the first ``1 - tail_share`` of the mass,
    then a Pareto tail of shape ``tail_alpha`` from ``body_max``, clipped at
    ``max``. Without a tail the body reaches ``max``."""
    lo, hi = spec["min"], spec["max"]
    share = spec.get("tail_share", 0.0)
    body_hi = spec.get("body_max", hi) if share > 0 else hi
    if u < 1.0 - share:
        v = u / (1.0 - share)
        x = lo * (body_hi / lo) ** v
    else:
        v = (u - (1.0 - share)) / share
        x = body_hi * (1.0 - v) ** (-1.0 / spec["tail_alpha"])
    return int(min(max(round(x), lo), hi))


def block_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    return [length_quantile(spec, (j + 0.5) / n) for j in range(n)]


def block_gaps(rate_per_s: float, n: int) -> List[float]:
    """``n`` exponential inter-arrival gaps as quantiles, scaled so that
    their mean is exactly ``1 / rate_per_s``."""
    raw = [-math.log(1.0 - (j + 0.5) / n) for j in range(n)]
    scale = n / (rate_per_s * sum(raw))
    return [g * scale for g in raw]


def token_batches(params: Dict[str, Any], seed: int, batch: int,
                  vocab: int):
    """Endless ``[batch, seq_len]`` int32 batches for training."""
    rng = rng_for(seed, 1)
    skew = params.get("unigram_skew", 1.0)
    while True:
        u = rng.random((batch, params["seq_len"]))
        yield np.minimum((vocab * u ** skew).astype(np.int32), vocab - 1)


@dataclass
class Request:
    index: int
    prompt_ids: List[int]
    max_new_tokens: int
    due_s: Optional[float]  # seconds after the load starts; None: closed loop
    shared_tokens: int = 0  # leading tokens shared with other requests


class RequestStream:
    """Requests of a ``requests`` mix, generated a block at a time.

    A block of ``block`` requests holds the ``block`` quantiles of every
    size distribution. It is dealt into ``block / shuffle`` sub-blocks, the
    s-th taking every ``block / shuffle``-th quantile from the s-th on, and
    the sub-blocks follow each other in that fixed order: whatever the seed,
    any ``shuffle`` consecutive requests hold the same sizes, so a window of
    some tens of requests holds the same work. The order within a sub-block
    is a permutation drawn from ``order_seed`` where the mix gives one (every
    ``--seed`` then offers the same sizes in the same order, and draws only
    the token ids and the weights), else from ``--seed``.

    With ``documents``, every sub-block opens ``shuffle / asks`` new
    documents (their first ask, which no cache can serve) and asks once
    about each document opened in the ``asks - 1`` sub-blocks before it: a
    document is asked ``asks`` times over ``asks`` consecutive sub-blocks,
    and ``shuffle`` documents are live at any time.
    """

    def __init__(self, params: Dict[str, Any], seed: int, vocab: int):
        self.params = params
        self.vocab = vocab
        self.block = int(params["block"])
        self.shuffle = int(params.get("shuffle", self.block))
        # one order for every seed where the mix fixes it (``order_seed``)
        self._order = rng_for(params.get("order_seed", seed), 2)
        self._ids = rng_for(seed, 3)
        self._lock = threading.Lock()
        self._ready: List[Request] = []
        self._n = 0
        self._clock = 0.0
        self._live_docs: List[List[List[int]]] = []  # newest sub-block last
        arrival = params["arrival"]
        self.closed_clients = (int(arrival["clients"])
                               if arrival["mode"] == "closed" else 0)
        self._gaps = (block_gaps(arrival["rate_per_s"], self.block)
                      if arrival["mode"] == "poisson" else None)

    def _tokens(self, n: int) -> List[int]:
        return self._ids.integers(1, self.vocab, size=n).tolist()

    def _shuffled(self, values: List[Any]) -> List[Any]:
        return [values[i] for i in self._order.permutation(len(values))]

    def _dealt(self, values: List[Any]) -> List[List[Any]]:
        """``values`` dealt into the block's sub-blocks, each shuffled."""
        n = len(values) * self.shuffle // self.block  # values a sub-block
        subs = len(values) // n
        return [self._shuffled(values[s::subs]) for s in range(subs)]

    def _make_block(self) -> None:
        p, k = self.params, self.block
        outs = self._dealt(block_lengths(p["output_tokens"], k))
        tails = self._dealt(block_lengths(p["prompt_tokens"], k))
        gaps = (self._dealt(self._gaps) if self._gaps
                else [[None] * self.shuffle] * len(outs))
        docs = p.get("documents")
        new_docs = (self._dealt(block_lengths(
            docs["tokens"], k // int(docs["asks"]))) if docs else None)
        for s in range(len(outs)):
            if docs:
                self._live_docs.append(
                    [self._tokens(n) for n in new_docs[s]])
                del self._live_docs[:-int(docs["asks"])]
                asked = self._shuffled(
                    [d for group in self._live_docs for d in group])
                prompts = [(d + self._tokens(t), len(d))
                           for d, t in zip(asked, tails[s])]
            else:
                prompts = [(self._tokens(n), 0) for n in tails[s]]
            for (ids, shared), out, gap in zip(prompts, outs[s], gaps[s]):
                if gap is not None:
                    self._clock += gap
                self._ready.append(Request(
                    self._n, ids, out,
                    self._clock if gap is not None else None, shared))
                self._n += 1

    def next(self) -> Request:
        with self._lock:
            if not self._ready:
                self._make_block()
            return self._ready.pop(0)
