"""Hyperparameter search with Tune: ASHA early stopping over a grid+random
space, TPE searcher, and experiment restore.

Run: JAX_PLATFORMS=cpu python examples/tune_asha.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import ray_tpu
    from ray_tpu import tune
    from ray_tpu.tune import ASHAScheduler, TuneConfig, Tuner

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)

    def objective(config):
        # a bowl with its minimum at (lr=0.01, width=32); report a few
        # steps so ASHA can cut the bad trials early
        for step in range(10):
            score = ((config["lr"] - 0.01) ** 2
                     + (config["width"] - 32) ** 2 / 1024
                     + 1.0 / (step + 1))
            tune.report({"score": score, "training_iteration": step + 1})

    tuner = Tuner(
        objective,
        param_space={
            "lr": tune.loguniform(1e-4, 1e-1),
            "width": tune.choice([8, 16, 32, 64]),
        },
        tune_config=TuneConfig(
            num_samples=8,
            metric="score",
            mode="min",
            scheduler=ASHAScheduler(max_t=10, grace_period=2),
        ),
    )
    results = tuner.fit()
    best = results.get_best_result()
    print("best config:", best.config, "score:",
          round(best.metrics["score"], 4))
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
