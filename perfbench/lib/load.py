"""Offering load and timing it from the client's side.

An open loop sends each request when it is due, whether or not earlier ones
were answered, and times it from when it was DUE (so a stall charges the
requests behind it); how late the generator ran is reported. A closed loop
keeps ``clients`` callers, each sending its next request when the last was
answered. One thread waits on each request's stream (the handle's streams
are consumed by blocking iteration); they sleep in the transport, so the
load costs little CPU.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from perfbench.lib import stats
from perfbench.lib.traffic import Request, RequestStream


class Record:
    __slots__ = ("request", "due", "sent", "stamps", "tokens", "error",
                 "done")

    def __init__(self, request: Request, due: float):
        self.request = request
        self.due = due
        self.sent: Optional[float] = None
        self.stamps: List[float] = []
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.done = False


class Load:
    """Runs a ``RequestStream`` against ``send(request) -> iterator of
    chunks`` until ``stop()``; every request's record is kept."""

    def __init__(self, stream: RequestStream,
                 send: Callable[[Request], Any]):
        self.stream = stream
        self.send = send
        self.records: List[Record] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.t_start = 0.0

    def _one(self, rec: Record) -> None:
        rec.sent = time.perf_counter()
        try:
            for chunk in self.send(rec.request):
                rec.stamps.append(time.perf_counter())
                rec.tokens.extend(chunk)
        except Exception as e:  # a failed request is a result, not a crash
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = True

    def _record(self, request: Request, due: float) -> Record:
        rec = Record(request, due)
        with self._lock:
            self.records.append(rec)
        return rec

    def _open_loop(self) -> None:
        while not self._stop.is_set():
            request = self.stream.next()
            due = self.t_start + request.due_s
            delay = due - time.perf_counter()
            if delay > 0 and self._stop.wait(delay):
                return
            rec = self._record(request, due)
            t = threading.Thread(target=self._one, args=(rec,), daemon=True)
            t.start()
            self._threads.append(t)

    def _client(self) -> None:
        while not self._stop.is_set():
            rec = self._record(self.stream.next(), time.perf_counter())
            self._one(rec)

    def start(self) -> None:
        self.t_start = time.perf_counter()
        n = self.stream.closed_clients
        targets = [self._client] * n if n else [self._open_loop]
        for target in targets:
            t = threading.Thread(target=target, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self, grace_s: float) -> None:
        """No new requests; wait up to ``grace_s`` for those in flight."""
        self._stop.set()
        deadline = time.perf_counter() + grace_s
        for t in list(self._threads):
            t.join(max(deadline - time.perf_counter(), 0.0))

    def snapshot(self) -> List[Record]:
        with self._lock:
            return list(self.records)


def window_results(records: List[Record], t0: float,
                   t1: float) -> Dict[str, Any]:
    """What the clients saw in the window ``[t0, t1]``: requests DUE inside
    it are the attempted ones (one that failed, never finished or came back
    with another number of tokens than it asked for has failed); tokens and
    gaps are counted by when they arrived."""
    due = [r for r in records if t0 <= r.due <= t1]
    failed = [r for r in due if r.error or not r.done
              or len(r.tokens) != r.request.max_new_tokens]
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in due if r.stamps]
    tokens = sum(1 for r in records for s in r.stamps if t0 <= s <= t1)
    gaps = [(b - a) * 1e3 for r in records
            for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= b <= t1]
    late = [(r.sent - r.due) * 1e3 for r in due if r.sent is not None]
    out = {
        "attempted": len(due), "failed": len(failed),
        "errors": sorted({r.error for r in failed if r.error})[:3],
        "tokens": tokens,
        "prompt_tokens": sum(len(r.request.prompt_ids) for r in due),
        "serve_tokens_per_s": tokens / (t1 - t0),
        "generator_late_p95_ms": stats.percentile(late, 95) if late else None,
        "n_gaps": len(gaps), "n_ttft": len(ttft),
    }
    if ttft:
        out["ttft_p95_ms"] = stats.percentile(ttft, 95)
        out["ttft_p50_ms"] = stats.percentile(ttft, 50)
    if gaps:
        out["gap_p95_ms"] = stats.percentile(gaps, 95)
        out["gap_p50_ms"] = stats.percentile(gaps, 50)
    return out


def attention_work(records: List[Record], t0: float, t1: float,
                   prefill_chunk: int) -> Dict[str, Any]:
    """What attention had to do in ``[t0, t1]``, from the client's records.
    ``decode_context_tokens``: for every token delivered then (but a
    request's first, which prefill yields) the length of its context, which
    a decode step must read. ``prefill_chunks``: for every request whose
    first token arrived then, its prompt cut into chunks, each as
    ``[query tokens, context tokens after the chunk]`` (no prefix hit is
    assumed, which holds where prompts are unshared)."""
    decode = sum(len(r.request.prompt_ids) + i
                 for r in records for i, s in enumerate(r.stamps)
                 if i >= 1 and t0 <= s <= t1)
    chunks = []
    for r in records:
        if r.stamps and t0 <= r.stamps[0] <= t1:
            n = len(r.request.prompt_ids)
            chunks += [[min(prefill_chunk, n - c), min(c + prefill_chunk, n)]
                       for c in range(0, n, prefill_chunk)]
    return {"decode_context_tokens": decode, "prefill_chunks": chunks}
