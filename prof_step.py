"""Perf probe for the GPT-2s train step on the local chip.

Usage: python prof_step.py <remat: none|dots|full> <batch> [scan|unroll]
         [ce_chunk] [trace]

`trace` writes a profiler trace of three steps under chiprun_out/ (the
directory the chip tool brings back).
"""
import os, sys, time
from ray_tpu._private import compile_cache
compile_cache.enable()
import jax
from ray_tpu.models import gpt2_small
from ray_tpu.models.training import OptimizerConfig, init_train_state, make_train_step

mode = sys.argv[1] if len(sys.argv) > 1 else "dots"
batch = int(sys.argv[2]) if len(sys.argv) > 2 else 16
scan = (sys.argv[3] != "unroll") if len(sys.argv) > 3 else True
ce_chunk = int(sys.argv[4]) if len(sys.argv) > 4 else 2048
kw = dict(remat=False) if mode == "none" else dict(remat_policy=mode)
cfg = gpt2_small(scan_layers=scan, ce_chunk=ce_chunk, **kw)
ocfg = OptimizerConfig(warmup_steps=10, decay_steps=1000)
state, tx = init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
step = make_train_step(cfg, tx)
tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, 1024), 0, cfg.vocab_size)
b = {"tokens": tokens}
state, m = step(state, b)
jax.block_until_ready(m["loss"])
t0 = time.perf_counter()
for _ in range(10):
    state, m = step(state, b)
jax.block_until_ready(m["loss"])  # jax returns before the device is done
dt = (time.perf_counter() - t0) / 10
device = jax.devices()[0]
print(f"device={device.platform}/{device.device_kind} mode={mode} "
      f"batch={batch} scan={scan} ce_chunk={ce_chunk} "
      f"step_ms={dt*1e3:.2f} tok/s={batch*1024/dt:.0f}")
if "trace" in sys.argv:
    trace_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "chiprun_out", "prof_step_trace")
    with jax.profiler.trace(trace_dir):
        for _ in range(3):
            state, m = step(state, b)
        jax.block_until_ready(m["loss"])
    print(f"trace written to {trace_dir}")
