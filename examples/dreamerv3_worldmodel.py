"""DreamerV3: learn a world model from replayed sequences, act from it.

The learner fits an RSSM world model (GRU + categorical latents) to
random-policy sequences of a goal-reading toy env, trains an
actor-critic purely on IMAGINED rollouts (no additional env steps), and
then the greedy policy solves the env — the model-based RL loop, with
all three phases (world-model fit, imagination, actor/critic update)
scanned into one jitted device program per ``update()``.

Run: JAX_PLATFORMS=cpu python examples/dreamerv3_worldmodel.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np

from ray_tpu.rllib import DreamerV3Learner

N_ACTIONS, NOISE, T = 4, 2, 8


def rollout(rng, batch):
    """Random-policy sequences: obs one-hot-encodes a per-episode goal
    action; acting the goal yields reward 1 with the NEXT observation."""
    goals = rng.integers(0, N_ACTIONS, size=batch)
    obs = np.zeros((batch, T, N_ACTIONS + NOISE), np.float32)
    for b in range(batch):
        obs[b, :, goals[b]] = 1.0
    obs[:, :, N_ACTIONS:] = 0.3 * rng.standard_normal(
        (batch, T, NOISE)).astype(np.float32)
    actions = rng.integers(0, N_ACTIONS, size=(batch, T)).astype(np.int32)
    rewards = np.zeros((batch, T), np.float32)
    rewards[:, 1:] = (actions[:, :-1] == goals[:, None]).astype(np.float32)
    return {"obs": obs, "actions": actions, "rewards": rewards,
            "continues": np.ones((batch, T), np.float32)}, goals


rng = np.random.default_rng(0)
learner = DreamerV3Learner(
    {"observation_dim": N_ACTIONS + NOISE, "action_dim": N_ACTIONS},
    {"deter": 64, "hidden": 64, "groups": 4, "classes": 4, "horizon": 5,
     "wm_lr": 3e-3, "actor_lr": 3e-3, "entropy_coef": 1e-2})

for i in range(250):
    batch, _ = rollout(rng, 16)
    m = learner.update(batch)
    if i % 50 == 0:
        print(f"update {i:3d}  wm_loss {m['wm_loss']:.3f}  "
              f"imagined_return {m['imag_return']:.2f}  "
              f"entropy {m['actor_entropy']:.2f}")

# evaluate the greedy policy (acts via posterior filtering of real obs)
batch, goals = rollout(rng, 64)
state = learner.policy_state(64)
prev_a = np.zeros(64, np.int64)
hits = 0
for t in range(T):
    state, a = learner.act(state, batch["obs"][:, t], prev_a, greedy=True)
    hits += int((np.asarray(a) == goals).sum())
    prev_a = np.asarray(a)
print(f"greedy hit rate {hits / (64 * T):.2f} (random would be "
      f"{1 / N_ACTIONS:.2f}) — learned entirely from imagination")
