"""Speculative decoding for the continuous-batching scheduler (ISSUE 18).

A small DRAFTER model proposes k tokens per active slot; the target model
scores all k (plus the bonus position) in ONE fixed-shape
``paged_verify_step`` call over the slots axis — a k+1-token
prefill-shaped program, the third (and only third) compiled program next
to the scheduler's prefill/decode pair. Acceptance is the exact
algorithm of arXiv:2211.17192: accept the longest draft prefix whose
tokens survive the q/p coin flips, resample the first rejection from the
corrected distribution max(q - p, 0), and sample the bonus token from
the target when every draft survives — so the OUTPUT DISTRIBUTION is
identically the target model's, and at temperature 0 the emitted tokens
are bit-exactly the sequential greedy path's.

The drafter owns a contiguous ``SlotKVCache`` arena (its own two jitted
programs) mirroring the scheduler's slot assignment. Its params come
from the shared weights arena (PR-9 ``get_or_publish``); the special
drafter ``"self"`` reuses the target's own device params, in which case
a slot's drafter KV is ADOPTED from the target's paged cache by an
eager gather (no drafter prefill — the prefix-cache TTFT win survives),
otherwise the drafter prefills the prompt through its own model.
Rejected drafts rewind cursors only — never pages: stale KV past a
cursor is causally masked until overwritten (the arena's standing
update-before-attend invariant).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu._private.metrics import Counter

m_spec_drafted = Counter(
    "ray_tpu_serve_spec_drafted_tokens_total",
    "Draft tokens proposed by the speculative drafter")
m_spec_accepted = Counter(
    "ray_tpu_serve_spec_accepted_tokens_total",
    "Draft tokens accepted by target-model verification")


def _softmax(logits_row, temperature: float) -> np.ndarray:
    x = np.asarray(logits_row, np.float64) / temperature
    x -= x.max()
    p = np.exp(x)
    return p / p.sum()


def accept_sample(draft_tokens: Sequence[int], p_draft, p_target,
                  rng) -> Tuple[int, List[int]]:
    """Exact speculative acceptance (temperature > 0).

    draft_tokens: the k proposed tokens. p_draft: [k, V] drafter
    probabilities (row j is the distribution d_{j+1} was sampled from).
    p_target: [k+1, V] target probabilities (row j scores position j;
    row k is the bonus distribution valid only when every draft is
    accepted). Returns ``(accepted, emitted)`` where emitted is
    ``drafts[:accepted] + [corrected-or-bonus token]`` — always exactly
    one more token than accepted, matching what sequential sampling from
    the target would emit in distribution."""
    k = len(draft_tokens)
    for j in range(k):
        d = int(draft_tokens[j])
        q = float(p_target[j][d])
        p = float(p_draft[j][d])
        if p > 0.0 and rng.uniform() < min(1.0, q / p):
            continue
        resid = np.maximum(np.asarray(p_target[j], np.float64)
                           - np.asarray(p_draft[j], np.float64), 0.0)
        s = resid.sum()
        if s <= 0.0:
            # q == p pointwise (possible up to float round-off): any
            # sample from q is exact
            tok = int(rng.choice(len(resid), p=np.asarray(p_target[j],
                                                          np.float64)
                                 / np.asarray(p_target[j],
                                              np.float64).sum()))
        else:
            tok = int(rng.choice(len(resid), p=resid / s))
        return j, [int(t) for t in draft_tokens[:j]] + [tok]
    pt = np.asarray(p_target[k], np.float64)
    tok = int(rng.choice(len(pt), p=pt / pt.sum()))
    return k, [int(t) for t in draft_tokens] + [tok]


def accept_greedy(draft_tokens: Sequence[int],
                  target_logits) -> Tuple[int, List[int]]:
    """Temperature-0 acceptance: accept the longest prefix where each
    draft equals the target argmax, then emit the target argmax at the
    first divergence (or the bonus argmax after a full accept). This IS
    what the sequential greedy loop emits, token for token — argmax over
    the same logits rows the single-token program would produce."""
    k = len(draft_tokens)
    emitted: List[int] = []
    for j in range(k):
        t = int(np.asarray(target_logits[j]).argmax())
        if t != int(draft_tokens[j]):
            return j, emitted + [t]
        emitted.append(t)
    bonus = int(np.asarray(target_logits[k]).argmax())
    return k, emitted + [bonus]


class Drafter:
    """The drafter's model state: params + a contiguous ``SlotKVCache``
    arena sharing the scheduler's slot numbering, plus its own two
    jitted programs (one prefill chunk shape, one [slots] decode shape).
    All methods run on the scheduler thread."""

    def __init__(self, cfg, params, *, slots: int, arena_len: int,
                 name: str = "self", shares_target: bool = False):
        import jax

        from ray_tpu.models.decode import (init_slot_caches,
                                           prefill_into_slot,
                                           slot_decode_step)

        self.cfg = cfg
        self.params = params
        self.name = name
        # True iff ``params`` are (a shared copy of) the TARGET's params:
        # only then is the target's paged KV the drafter's own KV and
        # adoption-by-gather is valid
        self.shares_target = shares_target
        self.slots = slots
        self.arena_len = arena_len
        self._jax = jax
        self._prefill = jax.jit(partial(prefill_into_slot, cfg),
                                donate_argnums=(4,))
        self._step = jax.jit(partial(slot_decode_step, cfg),
                             donate_argnums=(3,))
        self._caches = init_slot_caches(cfg, slots, arena_len)

    # ------------------------------------------------------------ state

    def lengths(self) -> np.ndarray:
        return np.asarray(self._caches[0].lengths)

    def set_lengths(self, new_lengths) -> None:
        """Host-side cursor rewind after a verify round (rejected drafts'
        KV stays, masked until overwritten). One device buffer PER layer:
        the drafter's step donates its caches and a shared buffer would
        be donated once per layer."""
        import jax.numpy as jnp

        host = np.asarray(new_lengths, np.int32)
        self._caches = [dataclasses.replace(c, lengths=jnp.asarray(host))
                        for c in self._caches]

    def reset_slot(self, slot: int) -> None:
        self._caches = [
            dataclasses.replace(c, lengths=c.lengths.at[slot].set(0))
            for c in self._caches]

    # ----------------------------------------------------- slot priming

    def adopt_from_paged(self, slot: int, target_caches, read_row,
                         length: int, page_tokens: int) -> None:
        """Prime a slot by copying the target's paged KV for positions
        [0, length) into the drafter's contiguous row — valid ONLY when
        the drafter shares the target's params (then target KV == the KV
        this drafter would have computed, bit for bit). Eager gather, no
        program compilation."""
        if not self.shares_target:
            raise RuntimeError(
                "adopt_from_paged requires a drafter sharing the target's "
                "params (drafter='self')")
        import jax.numpy as jnp

        idx = jnp.asarray(np.asarray(read_row, np.int32))
        out = []
        for dc, tc in zip(self._caches, target_caches):
            H, D = dc.k.shape[2:]  # target pages keep heads joined
            vk = tc.k[idx].reshape(-1, H, D)[:length]
            vv = tc.v[idx].reshape(-1, H, D)[:length]
            out.append(dataclasses.replace(
                dc,
                k=dc.k.at[slot, :length].set(vk.astype(dc.k.dtype)),
                v=dc.v.at[slot, :length].set(vv.astype(dc.v.dtype)),
                lengths=dc.lengths.at[slot].set(np.int32(length))))
        self._caches = out

    def prefill_prompt(self, slot: int, tokens: Sequence[int],
                       chunk: int) -> None:
        """Prime a slot by running the prompt through the DRAFTER model
        in fixed-width chunks (a distinct drafter cannot adopt the
        target's KV — different model, different cache). One compiled
        shape: the scheduler always passes its own prefill_chunk."""
        import jax.numpy as jnp

        self.reset_slot(slot)
        rest = list(tokens)
        while rest:
            piece = rest[:chunk]
            rest = rest[chunk:]
            real = len(piece)
            padded = piece + [0] * (chunk - real)
            _, self._caches = self._prefill(
                self.params, jnp.asarray([padded], jnp.int32),
                np.int32(real), np.int32(slot), self._caches)

    # ------------------------------------------------------------- step

    def step(self, tokens: np.ndarray, active: np.ndarray):
        """One batched drafter decode step over all slots. Returns the
        [slots, vocab] logits as numpy (the host samples drafts)."""
        import jax.numpy as jnp

        logits, self._caches = self._step(
            self.params, jnp.asarray(tokens), jnp.asarray(active),
            self._caches)
        return np.asarray(logits)

    def compiled_programs(self) -> int:
        return int(self._prefill._cache_size() + self._step._cache_size())
