"""RLlib throughput harness: env-steps/sec, dynamic loop vs Podracer.

Three sections, one JSON record line each (bench.py artifact shape),
each stamped with the device of the process that ran its model: this
process for PPO's local learner and for Anakin; the CPU backend for the
Podracer arms, whose learner and runner actors lease no chip and are
therefore held to `JAX_PLATFORMS=cpu` by their supervisor:

  * `ppo_atari_env_steps_per_sec` — the BASELINE "PPO-Atari
    env-steps/sec/chip" row: PPO + Nature-CNN over 84x84x4 uint8 frames
    (SyntheticAtari-v0 standing in for ALE; pass --env ALE/Breakout-v5
    where installed). Reference: tuned Ray+GPU PPO Atari sits at O(10k)
    env-steps/s per GPU; vs_baseline is value / 10_000.
  * `rl_{dynamic,sebulba}_env_steps_per_sec` + `podracer_speedup` — the
    SAME actor topology (R runner actors + 1 learner actor, IMPALA)
    through the dynamic loop (rollouts via object-store put/get, weight
    sync via the control plane) vs the Sebulba channel-streamed path.
    Trivial compute (tiny MLP, short fragments) per the pipeline-probe
    idiom, so the ratio isolates the framework term both paths add to
    the same jitted math. Fallback guards: the sebulba run must be
    channel-backed and every steady report must carry a zero
    rpc-counter delta.
  * `anakin_env_steps_per_sec` — the co-located fused topology
    (env.step + grad step in one jitted program over the pure-JAX
    SyntheticAtari dynamics).
"""

from __future__ import annotations

import argparse
import json
import time


def run(env: str = "SyntheticAtari-v0", iters: int = 5,
        num_env_runners: int = 2, num_envs: int = 8,
        rollout: int = 32) -> dict:
    """Dynamic-loop PPO over Atari-shaped frames (the BASELINE row)."""
    import ray_tpu
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    started_cluster = False
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=max(4, num_env_runners + 2))
        started_cluster = True
    try:
        algo = (PPOConfig()
                .environment(env=env)
                .env_runners(num_env_runners=num_env_runners,
                             num_envs_per_env_runner=num_envs,
                             rollout_fragment_length=rollout)
                .training(train_batch_size=rollout * num_envs,
                          minibatch_size=256, num_epochs=2)
                .build())
        try:
            algo.train()  # warmup: compiles sample + update programs
            t0 = time.perf_counter()
            for _ in range(iters):
                algo.train()
            dt = time.perf_counter() - t0
        finally:
            algo.stop()
    finally:
        if started_cluster:
            ray_tpu.shutdown()

    steps = iters * rollout * num_envs * max(1, num_env_runners)
    sps = steps / dt
    return {
        "metric": "ppo_atari_env_steps_per_sec",
        "value": round(sps, 1),
        "unit": "env_steps/s",
        "vs_baseline": round(sps / 10_000, 4),
        "detail": {"env": env, "iters": iters, "runners": num_env_runners,
                   "envs_per_runner": num_envs, "rollout": rollout,
                   "total_steps": steps, "elapsed_s": round(dt, 2)},
    }


def run_podracer(runners: int = 6, rollout: int = 2, iters: int = 80,
                 broadcast_interval: int = 48, depth: int = 8) -> list:
    """Dynamic actor-learner loop vs the Sebulba topology, identical
    configs and batch accounting. Returns three records."""
    import ray_tpu
    from ray_tpu.rllib import IMPALAConfig

    started_cluster = False
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=max(8, runners + 4))
        started_cluster = True

    def cfg(topology):
        return (IMPALAConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=runners,
                             num_envs_per_env_runner=1,
                             rollout_fragment_length=rollout)
                .training(num_batches_per_iteration=runners,
                          broadcast_interval=broadcast_interval,
                          model={"hiddens": (4,)})
                .learners(topology=topology, num_learners=1,
                          podracer_channel_depth=depth)
                .debugging(seed=0))

    steps_per_iter = runners * rollout  # 1 env per runner

    def measure(topology):
        algo = cfg(topology).build()
        try:
            if topology == "sebulba":
                topo = algo._podracer
                assert topo.is_channel_backed, (
                    "sebulba run is not channel-backed")
                assert topo.channel_depth > 1, (
                    "sebulba run lost its slot ring")
            for _ in range(10):  # warm: jits, pins, rendezvous
                algo.train()
            t0 = time.perf_counter()
            for _ in range(iters):
                out = algo.train()
                if topology == "sebulba":
                    for rep in out["reports"]:
                        assert rep["rpc_calls"] == 0 and \
                            rep["runner_rpc_calls"] == 0, (
                                "steady sebulba iteration issued "
                                "control-plane RPCs")
            dt = time.perf_counter() - t0
        finally:
            algo.stop()
        return iters * steps_per_iter / dt

    try:
        dyn_sps = measure("dynamic")
        seb_sps = measure("sebulba")
    finally:
        if started_cluster:
            ray_tpu.shutdown()

    detail = {"algo": "IMPALA", "env": "CartPole-v1", "runners": runners,
              "rollout": rollout, "iters": iters,
              "broadcast_interval": broadcast_interval,
              "channel_depth": depth,
              "note": "trivial-compute framework-term comparison; both "
                      "paths run identical jitted math on identical "
                      "batch counts"}
    return [
        {"metric": "rl_dynamic_env_steps_per_sec",
         "value": round(dyn_sps, 1), "unit": "env_steps/s",
         "detail": detail},
        {"metric": "rl_sebulba_env_steps_per_sec",
         "value": round(seb_sps, 1), "unit": "env_steps/s",
         "detail": detail},
        {"metric": "podracer_speedup",
         "value": round(seb_sps / max(dyn_sps, 1e-9), 2), "unit": "x",
         "detail": detail},
    ]


def run_anakin(num_envs: int = 32, rollout: int = 16,
               iters: int = 20) -> dict:
    """Fused co-located env+learner over the full Atari frame shape."""
    from ray_tpu.rllib import AnakinTrainer

    trainer = AnakinTrainer(num_envs=num_envs, rollout=rollout, seed=0)
    trainer.train(2)  # compile + warm
    out = trainer.train(iters)
    return {
        "metric": "anakin_env_steps_per_sec",
        "value": round(out["env_steps_per_sec"], 1),
        "unit": "env_steps/s",
        "detail": {"num_envs": num_envs, "rollout": rollout,
                   "iters": iters, "obs": "84x84x4 uint8 (Nature CNN)",
                   "total_loss": round(out["total_loss"], 4)},
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="SyntheticAtari-v0")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--runners", type=int, default=2)
    ap.add_argument("--envs", type=int, default=8)
    ap.add_argument("--rollout", type=int, default=32)
    ap.add_argument("--skip-ppo", action="store_true")
    ap.add_argument("--skip-podracer", action="store_true")
    ap.add_argument("--skip-anakin", action="store_true")
    ap.add_argument("--podracer-runners", type=int, default=6)
    ap.add_argument("--podracer-iters", type=int, default=80)
    ap.add_argument("--anakin-envs", type=int, default=32)
    ns = ap.parse_args()

    records = []
    if not ns.skip_ppo:
        records.append(run(ns.env, ns.iters, ns.runners, ns.envs,
                           ns.rollout))
    if not ns.skip_podracer:
        records.extend(run_podracer(runners=ns.podracer_runners,
                                    iters=ns.podracer_iters))
    if not ns.skip_anakin:
        records.append(run_anakin(num_envs=ns.anakin_envs))
    import jax

    here = {"device": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind}
    actors = {"device": "cpu", "device_kind": "cpu"}
    for rec in records:
        rec.update(actors if rec["metric"].startswith(("rl_", "podracer_"))
                   else here)
        print(json.dumps(rec))
