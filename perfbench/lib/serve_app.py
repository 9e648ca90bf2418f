"""The replica the serving cells deploy: ``LLMServerImpl`` plus what only
the process that holds the chip can do — make the weights on the device from
the seed, check itself against the plain reference, trace its own device
and reduce the trace. Requests take the program's normal path (``__call__``
is inherited untouched): handle, router, replica, scheduler, paged cache.
"""

from __future__ import annotations

import os
import time
from functools import partial
from typing import Any, Dict, List

from ray_tpu.serve.llm import LLMServerImpl


def _ids(ids) -> List[int]:
    """Detokenizer that hands the token ids through: the benchmark checks
    tokens, not text."""
    return [int(i) for i in ids]


class BenchLLMServer(LLMServerImpl):
    def __init__(self, *, bench: Dict[str, Any], **kwargs):
        self._bench_first_line = time.time()
        import jax.numpy as jnp

        from perfbench.lib import configs, weights, worker

        self._bench = bench
        self._bench_compiles = worker.CompileCounter()
        self._bench_stamp = worker.device_stamp(bench["require_tpu"])
        self._bench_chip_open = time.time()
        super().__init__(
            preset=bench["preset"],
            preset_overrides=configs.with_dtypes(bench["overrides"], jnp),
            params_loader=partial(weights.make_params, seed=bench["seed"]),
            share_weights=False, detokenize=_ids, **kwargs)
        self._bench_ready = time.time()

    def bench_info(self) -> Dict[str, Any]:
        from perfbench.lib import configs, hostwatch, worker

        return {"first_line": self._bench_first_line,
                "host": hostwatch.process_reading(),
                "chip_open": self._bench_chip_open,
                "ready": self._bench_ready,
                "device": {**self._bench_stamp,
                           "memory_peak_bytes": worker.memory_peak_bytes()},
                "sizes": configs.program_sizes(self.cfg),
                "compiles": self._bench_compiles.count,
                "memory_stats": dict(
                    self._jax.devices()[0].memory_stats() or {})}

    def reference_check(self, prompt: List[int], served: List[int],
                        hp: Dict[str, Any],
                        reference_path: str) -> Dict[str, Any]:
        """The system against the plain reference on this replica's own
        weights, for one prompt and the tokens the served path answered it
        with (temperature 0). Two comparisons on logits, none on sampled
        tokens:
          logits  ``prefill`` then ``decode_step`` through the cache, against
                  the reference's full forward pass at the same positions
          margin  each served token's reference logit against the
                  reference's largest at that position: the paged path
                  (prefill chunks, paged decode, the kernel) may pick another
                  token only where rounding can
        Both as a share of the largest reference logit in magnitude;
        ``logit_rms_err`` is the root-mean-square error over the
        root-mean-square logit."""
        import jax.numpy as jnp
        import numpy as np

        from perfbench.lib import manifest as manifest_lib
        from ray_tpu.models.decode import init_caches

        ref = manifest_lib.load_module(reference_path, "perfbench_reference")
        fed = served[:-1]  # the last served token is never fed back
        tokens = jnp.asarray([list(prompt) + fed], jnp.int32)
        want = np.asarray(ref.forward(self.params, tokens, hp)[0],
                          np.float32)[len(prompt) - 1:]
        caches = init_caches(self.cfg, 1, len(prompt) + len(served))
        logits, caches = self._prefill(
            self.params, jnp.asarray([prompt], jnp.int32), caches)
        got = [np.asarray(logits[0], np.float32)]
        for t in fed:
            logits, caches = self._decode_step(
                self.params, jnp.asarray([[t]], jnp.int32), caches)
            got.append(np.asarray(logits[0], np.float32))
        scale = float(np.abs(want).max())
        diff = np.stack(got) - want
        logit_err = float(np.abs(diff).max() / scale)
        rms_err = float(np.sqrt((diff ** 2).mean() / (want ** 2).mean()))
        margins = [float((want[i].max() - want[i][t]) / scale)
                   for i, t in enumerate(served)]
        return {"logit_err": logit_err, "logit_rms_err": rms_err,
                "served_margin": max(margins),
                "positions": len(served), "prompt_tokens": len(prompt)}

    def trace_start(self) -> bool:
        from perfbench.lib import trace

        trace.start(self._bench["trace_dir"])
        return True

    def trace_stop(self) -> Any:
        from perfbench.lib import trace

        path = trace.stop(self._bench["trace_dir"])
        if os.environ.get("PERFBENCH_DESCRIBE_TRACE"):
            with open(os.environ["PERFBENCH_DESCRIBE_TRACE"], "w") as f:
                f.write(trace.describe(path, per_line=40))
        return trace.summarize(trace.load(path))
