"""Two ways to train PPO on CartPole: actor-based and fully-jitted Anakin.

Run: JAX_PLATFORMS=cpu python examples/ppo_cartpole.py --mode anakin
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_actor_based(iters: int):
    import ray_tpu
    from ray_tpu.rllib import PPOConfig

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_envs_per_env_runner=8,
                           rollout_fragment_length=256)
              .training(lr=3e-4, minibatch_size=256, num_epochs=8,
                        entropy_coeff=0.01))
    algo = config.build()
    for i in range(iters):
        result = algo.train()
        print(f"iter {i:3d}  return {result.get('episode_return_mean', 0):.1f}")
    algo.cleanup()
    ray_tpu.shutdown()


def run_anakin(iters: int):
    from ray_tpu.rllib import AnakinPPO

    algo = AnakinPPO("CartPole-v1", num_envs=64, rollout_len=64, lr=1e-3)
    for i in range(iters):
        metrics = algo.train()
        print(f"iter {i:3d}  return {metrics['episode_return_mean']:.1f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["actors", "anakin"], default="anakin")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    if args.mode == "actors":
        run_actor_based(args.iters)
    else:
        run_anakin(args.iters)
