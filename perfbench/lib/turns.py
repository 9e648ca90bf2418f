"""What the readers of the scheduler's own gaps share (PR 39).

The scheduler (``ray_tpu/serve/_private/continuous.py``) counts every
emitted token that is not its sequence's first, with the time between the
read that emitted it and the read that emitted the one before it, under one
of two kinds, decided from the prompt tokens it had dispatched in between and
never from a program's name: ``gap_plain_tokens`` / ``gap_plain_s`` (decode
work only) and ``gap_prefill_tokens`` / ``gap_prefill_s`` (prompt tokens
too). The readers take the window's deltas of these.

A program that reports none of the keys (the parent of PR 39, which the
driver runs traced with these readers laid over it) has nothing to read and
says so with 0, as ``layers.predates_phase_clock`` does. A program that
reports them and counted nothing in the window, or timed nothing (the
recorder off), returns nothing.

Until ``BENCHMARK.json`` lists the six names (``PERF.md`` 7), a recorded run
is read with

    python -m perfbench.lib.turns < output

which prints the three readings from the ``delta`` of the run's ``checks``
note (every serving run carries it, traced or not), and whether the window's
tokens add up: plain + prefill + first tokens = tokens generated.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, Optional

KINDS = ("plain", "prefill")


def predates_turns(ctx: Dict[str, Any]) -> bool:
    """True for a serving program that does not count its gaps yet."""
    return "gap_plain_tokens" not in ctx["counters"].get("end", {})


def _mean_ms(delta: Dict[str, Any], kind: str) -> Optional[float]:
    tokens = delta.get(f"gap_{kind}_tokens")
    seconds = delta.get(f"gap_{kind}_s")
    if not tokens or not seconds:
        return None
    return 1e3 * seconds / tokens


def _share_percent(delta: Dict[str, Any]) -> Optional[float]:
    counts = [delta.get(f"gap_{kind}_tokens", 0) for kind in KINDS]
    if not sum(counts):
        return None
    return 100.0 * counts[1] / sum(counts)


def mean_gap_ms(ctx: Dict[str, Any], kind: str) -> Optional[float]:
    """d``gap_<kind>_s`` over d``gap_<kind>_tokens``, in ms: the mean time
    between two reads for the tokens of that kind emitted in the window."""
    if predates_turns(ctx):
        return 0.0
    return _mean_ms(ctx["counters"].get("delta", {}), kind)


def prefill_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    """The share of the window's gaps during which the device was given
    prompt tokens too."""
    if predates_turns(ctx):
        return 0.0
    return _share_percent(ctx["counters"].get("delta", {}))


def readings(delta: Dict[str, Any]) -> Dict[str, Any]:
    """The three readings of one window's ``delta`` (a ``checks`` note leaves
    out a key whose difference is 0, so a note without either count is a
    program's that counts no gaps yet: 0), and whether its tokens add up."""
    gaps = sum(delta.get(f"gap_{kind}_tokens", 0) for kind in KINDS)
    counts = any(f"gap_{kind}_tokens" in delta for kind in KINDS)
    plain, prefill, share = ((_mean_ms(delta, "plain"),
                              _mean_ms(delta, "prefill"),
                              _share_percent(delta)) if counts
                             else (0.0, 0.0, 0.0))
    return {"sched.decode_turn_ms": plain, "sched.prefill_turn_ms": prefill,
            "sched.prefill_turn_share": share,
            "tokens_add_up": (gaps + delta.get("first_tokens", 0)
                              == delta.get("tokens_generated", 0)),
            "gaps": gaps, "turns": delta.get("turns", 0),
            "prefill_tokens": delta.get("prefill_tokens", 0)}


def main() -> int:
    notes = [json.loads(ln) for ln in sys.stdin
             if ln.startswith('{"note": "checks"')]
    if not notes or "delta" not in notes[-1]:
        print("turns: no checks note with a delta (a serving run has one)")
        return 1
    print(json.dumps(readings(notes[-1]["delta"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
