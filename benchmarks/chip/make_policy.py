#!/usr/bin/env python3
"""Train a configuration's Q-table by the paper's procedure and write the
snapshot its configuration names.

    python3 benchmarks/chip/make_policy.py --config dense_gmres_ir

`train_policy` (arXiv 2601.00728 Alg. 3) with the configuration's reward
weights, 100 episodes, 10x10 state bins and alpha 0.5, over `--systems`
systems drawn from the configuration's generator with its design seed
(the design's stratified (n, kappa) pairs). It runs the jnp precision
backend on the f32 carrier, the carrier the TPU path serves on, so it
needs no chip: a few minutes on a CPU host.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import bench  # noqa: E402


def _training_set(cfg: dict, count: int, seed: int):
    """`count` systems of the design's stratified (n, kappa) pairs, with
    matrices drawn from `seed`: the systems the policy was trained on
    (the benchmark's pool draws its own from the design seed + 2)."""
    import numpy as np
    gen = bench.module("generators", cfg["generator"]["kind"])
    rng = np.random.default_rng(seed)
    out = []
    for n, kappa in bench.design(cfg, count):
        A, b, x = gen.make(n, kappa, rng, cfg["generator"]["params"])
        out.append({"A": A, "b": b, "x_true": x, "n": n, "kappa": kappa})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--systems", type=int, default=64)
    ap.add_argument("--episodes", type=int, default=100)
    args = ap.parse_args(argv)

    from repro.core import reduced_action_space
    from repro.core.autotune import TrainConfig, train_policy
    from repro.core.features import system_features
    from repro.core.rewards import RewardConfig
    from repro.data.matrices import LinearSystem
    from repro.precision import JnpBackend

    cfg = bench.load_json(os.path.join(HERE, "configs",
                                       args.config + ".json"))
    seed = int(cfg["assumed"]["design_seed"])
    systems = [LinearSystem(s["A"], s["b"], s["x_true"], s["kappa"],
                            system_features(s["A"]), "bench")
               for s in _training_set(cfg, args.systems, seed)]
    space = reduced_action_space(tuple(cfg["action_space"]["ladder"]),
                                 cfg["action_space"]["k"])
    task = bench.module("tasks", cfg["task"]).build(
        cfg, backend=JnpBackend(carrier_dtype="float32"))
    task.instances = systems
    task.action_space = space
    policy, hist = train_policy(
        task, RewardConfig(**cfg["reward"]),
        TrainConfig(episodes=args.episodes, seed=seed))
    out = os.path.join(HERE, cfg["policy"])
    policy.save(out)
    print(f"{args.config}: {hist.n_solves} solves, "
          f"{hist.wall_time_s:.1f} s, last episode reward "
          f"{hist.episode_reward[-1]:.3f}, written to {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
