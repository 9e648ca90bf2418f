"""LLM decode deployment — the Serve flagship (BASELINE.md row 5).

The reference leaves model serving to torch/vLLM inside replicas (its
`ray.serve.llm` wraps vLLM engines); here the decode loop is TPU-native
and the batching is CONTINUOUS (iteration-level):

  * a PAGED KV pool (`models.decode.PagedKVCache`) plus ONE
    fixed-shape jitted decode step over all slots per iteration; slots
    own page tables instead of worst-case `max_seq_len` ranges (tables
    and cursors are the scheduler's host state, passed to each call: the
    device holds pages only), a radix prefix cache turns shared
    system-prompt/few-shot preambles into a page-table splice + cursor
    jump at admission, new requests are
    admitted into free slots between iterations (chunked prefill),
    finished/EOS/cancelled sequences retire their slot (and pages)
    immediately — ≈ vLLM's PagedAttention + SGLang's RadixAttention
    scheduling, not a flush-and-drain `@serve.batch` window; the
    paged-attention implementation (the Pallas kernel on a TPU, the
    pure-JAX reference elsewhere) is `ops.paged_attention.resolve_impl`'s
    choice;
  * token streaming: `{"prompt": ..., "stream": true}` returns an async
    generator consuming the scheduler's per-slot token queue — the stream
    rides the same batched program as everything else (no per-stream
    single-sequence decode loop, nothing jitted ever runs on the
    replica's asyncio event loop);
  * one-copy-per-node weights: the first replica on a node publishes the
    params into the shared-memory object arena; later same-node replicas
    attach pinned read-only views (serve/_private/weights.py), and new
    nodes can receive the tree over `collective.broadcast`
    (`push_weights`) so scale-up is seconds, not checkpoint-staging
    minutes;
  * replica autoscaling/health from the regular serve control plane.

The default preset is `llama_debug` (random weights) so the deployment
is runnable anywhere; pass `preset="llama3_8b"` plus a checkpoint
loader for the real thing.
"""

from __future__ import annotations

import asyncio
import time
from functools import partial
from typing import Any, Dict, List, Optional

import ray_tpu.serve as serve

# ray_tpu.models (and with it jax) is imported where a replica is built,
# never at module level: the driver that calls build_app() stays off JAX,
# so the chip is free for the replica's own process.


def _byte_tokenize(text: str, vocab_size: int) -> List[int]:
    """Byte-level toy tokenizer (debug presets have vocab >= 256). Real
    deployments pass `tokenize`/`detokenize` callables to LLMServer."""
    return [b % vocab_size for b in text.encode("utf-8")]


def _byte_detokenize(ids: List[int]) -> str:
    return bytes(int(i) % 256 for i in ids).decode("utf-8", errors="replace")


# A replica's build as leaf phases of one clock (``flight.PhaseClock``, as
# the scheduler thread's time is): the program's imports and the config; the
# loader and ``device_put``; the drafter, the scheduler and its pools. A
# phase ends where its last call RETURNS: device work it left running
# (``device_put``, a loader that fills its arrays on the device) belongs to
# whoever next waits for it, in the build or after it.
BUILD_PHASES = ("setup.config", "setup.weights", "setup.scheduler")
_B_CONFIG, _B_WEIGHTS, _B_SCHEDULER = range(3)
# a phase's seconds in scheduler_stats(): setup_config_s, ...
BUILD_KEYS = tuple(p.replace(".", "_") + "_s" for p in BUILD_PHASES)


class LLMServerImpl:
    """One model replica: owns the weights and the continuous-batching
    scheduler (``serve/_private/continuous.py``). Weights are shared per
    node through the object arena unless ``share_weights=False``."""

    def __init__(self, preset: str = "llama_debug",
                 preset_overrides: Optional[Dict[str, Any]] = None,
                 max_new_tokens: int = 16,
                 temperature: float = 0.0,
                 params_loader=None,
                 tokenize=None, detokenize=None,
                 slots: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 arena_len: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 share_weights: bool = True,
                 weights_key: Optional[str] = None,
                 weights_bcast: Optional[Dict[str, Any]] = None,
                 eos_id: Optional[int] = None,
                 drafter: Optional[str] = None,
                 spec_k: Optional[int] = None,
                 migration_budget: Optional[int] = None,
                 attn: Optional[str] = None):
        import jax

        from ray_tpu._private import compile_cache, flight

        compile_cache.watch()  # every program this process jits, by name
        # stopped on the way out; a build that raises leaves no replica
        self._build_clock = build = flight.PhaseClock(BUILD_PHASES)
        build.switch(_B_CONFIG)

        from ray_tpu.models import presets
        from ray_tpu.models.decode import decode_step, prefill
        from ray_tpu.models.transformer import init_params

        self._jax = jax
        # preset fields (e.g. a wider max_seq_len context window for long
        # few-shot preambles) are overridable per deployment; the KV arena
        # and admission limits follow cfg.max_seq_len automatically
        self.cfg = getattr(presets, preset)(**(preset_overrides or {}))
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self._eos_id = eos_id
        self._seq_counter = 0
        # the scheduler thread -> event loop hand-off: how long the items
        # received so far lay between the two (scheduler_stats())
        self._stream_lag_ns = 0
        self._stream_tokens = 0

        # ---- weights: one arena copy per node (ISSUE 9 tentpole) ----
        build.switch(_B_WEIGHTS)
        from ray_tpu.serve._private import weights as _weights

        def load():
            if weights_bcast is not None and weights_bcast.get("rank", 0) != \
                    weights_bcast.get("root", 0):
                # fresh node: receive the tree from an existing replica
                # instead of staging a checkpoint
                return _weights.broadcast_params(
                    None, weights_bcast["group"],
                    int(weights_bcast["world_size"]),
                    int(weights_bcast["rank"]),
                    root=int(weights_bcast.get("root", 0)))
            if params_loader is not None:
                return params_loader(self.cfg)
            return init_params(self.cfg, jax.random.PRNGKey(0))

        # a custom loader has no stable identity to share under; require an
        # explicit weights_key to opt in
        can_share = share_weights and (params_loader is None
                                       or weights_key is not None)
        if can_share:
            # preset overrides change the parameter shapes — fold them
            # into the default share key so differently-configured
            # deployments never attach to each other's arena copy
            ov = ""
            if preset_overrides:
                ov = ":" + ",".join(f"{k}={preset_overrides[k]}"
                                    for k in sorted(preset_overrides))
            key = weights_key or f"llm:{preset}{ov}:seed0"
            host, self._weights_info = _weights.get_or_publish(key, load)
        else:
            host, self._weights_info = load(), {"mode": "local",
                                                "shared": False}
        # one device copy per replica (HBM on TPU); the HOST copy stays
        # shared in the node arena — self._host_params keeps the read-only
        # views (and their pins) alive for this replica's lifetime
        self._host_params = host
        self.params = jax.device_put(host)
        del host

        build.switch(_B_SCHEDULER)
        self._tokenize = tokenize or partial(
            _byte_tokenize, vocab_size=self.cfg.vocab_size)
        self._detokenize = detokenize or _byte_detokenize
        # the router can only steer (prefix affinity) on prompts it can
        # tokenize itself — true for the reproducible byte tokenizer;
        # custom tokenizers need explicit prompt_ids in the request
        self._byte_tok = tokenize is None
        # the sequential cache's two programs: the oracle a deployment
        # checks its served tokens against (perfbench's reference check).
        # The caches are donated: the check runs beside the pool, and a
        # second cache of a looped model is half a gigabyte
        self._prefill = jax.jit(partial(prefill, self.cfg),
                                donate_argnums=(2,))
        self._decode_step = jax.jit(partial(decode_step, self.cfg),
                                    donate_argnums=(2,))

        from ray_tpu.serve._private.continuous import ContinuousScheduler

        drafter_obj = self._build_drafter(drafter, slots, arena_len,
                                          _weights)
        self._sched = ContinuousScheduler(
            self.cfg, self.params, slots=slots,
            prefill_chunk=prefill_chunk, arena_len=arena_len,
            eos_id=eos_id, page_tokens=page_tokens, kv_pages=kv_pages,
            prefix_cache=prefix_cache, drafter=drafter_obj,
            spec_k=spec_k, migration_budget=migration_budget,
            attn=attn)
        build.stop()

    def _build_drafter(self, drafter: Optional[str], slots, arena_len,
                       _weights):
        """Resolve the drafter knob (arg, else RAY_TPU_SERVE_DRAFTER; ""
        = off) into a ``speculative.Drafter``. ``"self"`` reuses this
        replica's own device params (zero extra weight memory, KV adopted
        from the paged cache); any other name is a preset whose weights
        come from the shared per-node arena like the target's
        (``get_or_publish``) — a drafter must share the target's
        vocabulary or its proposals would be meaningless token ids."""
        import jax

        from ray_tpu._private.config import global_config
        from ray_tpu.models import presets
        from ray_tpu.models.transformer import init_params

        conf = global_config()
        name = conf.serve_drafter if drafter is None else drafter
        if not name:
            return None
        slots_r = int(conf.serve_slots if slots is None else slots)
        arena_r = int(self.cfg.max_seq_len if arena_len is None
                      else arena_len)
        if name == "self":
            d_cfg, d_params, shares = self.cfg, self.params, True
        else:
            try:
                d_cfg = getattr(presets, name)()
            except AttributeError:
                raise ValueError(f"unknown drafter preset {name!r}")
            if d_cfg.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    f"drafter {name!r} vocab_size ({d_cfg.vocab_size}) != "
                    f"target vocab_size ({self.cfg.vocab_size})")
            d_host, self._drafter_weights_info = _weights.get_or_publish(
                f"llm:{name}:seed0",
                lambda: init_params(d_cfg, jax.random.PRNGKey(0)))
            self._drafter_host_params = d_host
            d_params = jax.device_put(d_host)
            shares = False
        if arena_r > d_cfg.max_seq_len:
            raise ValueError(
                f"drafter {name!r} max_seq_len ({d_cfg.max_seq_len}) is "
                f"shorter than the serving arena ({arena_r})")
        from ray_tpu.serve._private.speculative import Drafter

        return Drafter(d_cfg, d_params, slots=slots_r, arena_len=arena_r,
                       name=name, shares_target=shares)

    # ------------------------------------------------------- continuous

    def _submit(self, ids: List[int], max_new: int, temperature: float,
                fleet_hint=None):
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        self._seq_counter += 1
        seq = self._sched.submit(
            ids, max_new_tokens=max_new, temperature=temperature,
            seed=self._seq_counter, loop=loop, queue=q,
            fleet_hint=fleet_hint, request_id=self._seq_counter)
        return seq, q

    async def _next_item(self, q: asyncio.Queue):
        """The next ``(kind, value)`` the scheduler handed to this request;
        for a token, the time since the scheduler stamped it is counted
        (stamp 0: recorder off, nothing is counted)."""
        kind, val, stamp = await q.get()
        if stamp and kind == "tok":
            self._stream_lag_ns += time.perf_counter_ns() - stamp
            self._stream_tokens += 1
        return kind, val

    async def _run_continuous(self, ids: List[int], max_new: int,
                              temperature: float,
                              fleet_hint=None) -> List[int]:
        seq, q = self._submit(ids, max_new, temperature, fleet_hint)
        toks: List[int] = []
        try:
            while True:
                kind, val = await self._next_item(q)
                if kind == "tok":
                    toks.append(val)
                elif kind == "end":
                    return toks
                else:
                    raise RuntimeError(f"generation failed: {val}")
        except asyncio.CancelledError:
            self._sched.cancel(seq)
            raise

    async def _stream_continuous(self, ids: List[int], max_new: int,
                                 temperature: float, fleet_hint=None):
        """Streaming = a consumer of the scheduler's per-slot token queue.
        Abandoning the generator (consumer gone) cancels the sequence,
        which retires its slot on the scheduler's next iteration."""
        seq, q = self._submit(ids, max_new, temperature, fleet_hint)
        try:
            while True:
                kind, val = await self._next_item(q)
                if kind == "tok":
                    yield self._detokenize([val])
                elif kind == "end":
                    return
                else:
                    raise RuntimeError(f"generation failed: {val}")
        finally:
            self._sched.cancel(seq)

    # ------------------------------------------------------------ entry

    async def __call__(self, request: Optional[Dict[str, Any]] = None):
        request = request or {}
        if isinstance(request, str):
            request = {"prompt": request}
        prompt = request.get("prompt", "")
        if request.get("prompt_ids") is not None:
            # explicit token ids (custom-tokenizer clients; also what the
            # affinity router hashed, so steering and execution agree)
            ids = [int(t) for t in request["prompt_ids"]]
        else:
            ids = self._tokenize(prompt)
        if not ids:
            raise ValueError("prompt must be non-empty")
        max_new = int(request.get("max_new_tokens", self.max_new_tokens))
        temperature = float(request.get("temperature", self.temperature))
        # router-attached pull hint (fleet hit on another replica)
        fleet_hint = request.get("_fleet_hint")
        if request.get("stream"):
            return self._stream_continuous(ids, max_new, temperature,
                                           fleet_hint)
        out_ids = await self._run_continuous(ids, max_new, temperature,
                                             fleet_hint)
        return {"prompt": prompt, "text": self._detokenize(out_ids),
                "num_tokens": len(out_ids)}

    # ------------------------------------------------------ introspection

    def scheduler_stats(self) -> Dict[str, Any]:
        """The scheduler's ``stats()`` plus what only the replica knows:
        ``stream_lag_s`` / ``stream_tokens`` (the hand-off to the event
        loop), ``stream_reports`` / ``stream_items_reported`` (the way out
        of the worker), ``platform`` / ``device_kind`` / ``device_count`` /
        ``peak_bytes_in_use`` (where the model really runs),
        ``setup_config_s`` / ``setup_weights_s`` / ``setup_scheduler_s``
        (the build by phase, ``BUILD_KEYS``), and this PROCESS's compile
        record (``_private/compile_cache.py``): ``jit_compile_events``,
        ``jit_trace_s``, ``jit_lower_s``, ``jit_compile_s`` (compile or
        cache load), ``jit_cache_hits``, ``jit_cache_misses``,
        ``jit_cache_saved_s``, and ``jit_programs``, function name -> row.
        Two snapshots' difference of ``jit_compile_events`` is what was
        compiled between them; the rows whose ``n`` rose say which."""
        out = self._sched.stats()
        from ray_tpu._private import compile_cache

        out.update(compile_cache.record())
        out.update(zip(BUILD_KEYS, self._build_clock.seconds()))
        out["stream_lag_s"] = self._stream_lag_ns / 1e9
        out["stream_tokens"] = self._stream_tokens
        # how this worker's streams left it: items over reports is what one
        # turn of the replica's loop sent its callers in one call
        from ray_tpu._private import api

        if api._core is not None:
            out["stream_reports"] = api._core.stream_reports
            out["stream_items_reported"] = api._core.stream_items_reported
        # where the model really runs: a replica that was not given a
        # chip runs on the CPU, and the record has to say so
        devices = self._jax.devices()
        out["platform"] = devices[0].platform
        out["device_kind"] = devices[0].device_kind
        out["device_count"] = len(devices)
        out["peak_bytes_in_use"] = (devices[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        return out

    def queue_depth(self) -> int:
        """Admitted-but-unscheduled sequences (the replica relays this
        into its stats so the controller can autoscale on backlog, not
        just in-flight counts)."""
        return int(self._sched.stats().get("queue_depth", 0))

    def prefix_digest(self) -> Dict[str, Any]:
        """The radix cache's chain-hash digest plus what the router needs
        to hash prompts the same way (tokenizer kind + vocab). Empty when
        there is nothing advertisable (prefix cache off or empty)."""
        d = self._sched.prefix_digest()
        if d:
            d = dict(d)
            d["vocab_size"] = self.cfg.vocab_size
            d["tok"] = "byte" if self._byte_tok else "opaque"
        return d

    def export_prefix(self, tokens: List[int],
                      timeout_s: float = 30.0) -> Dict[str, Any]:
        """Peer-replica migration pull: the longest cached prefix of
        ``tokens`` as per-layer KV page arrays (replica→replica, never
        through the controller)."""
        return self._sched.export_prefix(list(tokens), timeout_s=timeout_s)

    def weights_info(self) -> Dict[str, Any]:
        return dict(self._weights_info)

    def push_weights(self, group: str, world_size: int,
                     rank: int = 0) -> bool:
        """Root side of seconds-scale scale-up: broadcast this replica's
        weights to `world_size - 1` receivers (replicas starting on new
        nodes with ``weights_bcast={"group", "world_size", "rank"}``)."""
        from ray_tpu.serve._private import weights as _weights

        _weights.broadcast_params(self._host_params, group, world_size,
                                  rank, root=rank)
        return True

    def check_health(self) -> bool:
        return not self._sched.closed and self.params is not None

    def shutdown(self) -> None:
        self._sched.shutdown()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


LLMServer = serve.deployment(name="llm", max_ongoing_requests=32)(
    LLMServerImpl)


def build_app(preset: str = "llama_debug", num_replicas: int = 1,
              max_new_tokens: int = 16, temperature: float = 0.0,
              **kwargs) -> "serve.Application":
    """`serve.run(build_app(...), route_prefix="/llm")` — the deployable
    LLM decode application."""
    import ray_tpu

    # one replica process for each chip: where the cluster shows TPU
    # chips a replica leases one (its worker is then pinned to it and the
    # model runs there); on a cluster without chips the same call runs on
    # the CPU. scheduler_stats() names the platform either way.
    actor_options = {}
    if (ray_tpu.is_initialized()
            and ray_tpu.cluster_resources().get("TPU", 0) >= 1):
        actor_options["num_tpus"] = 1
    dep = LLMServer.options(num_replicas=num_replicas,
                            ray_actor_options=actor_options)
    return dep.bind(preset=preset, max_new_tokens=max_new_tokens,
                    temperature=temperature, **kwargs)
