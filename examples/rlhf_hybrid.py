"""RLHF PPO loop with LoRA adapters on the hybrid engine (the reference's
DeepSpeed-Chat configuration, SURVEY.md §6).

The reference's DeepSpeed-Chat actor step (``blogs/deepspeed-chat/
README.md:41`` + ``runtime/hybrid_engine.py:32``): rollouts generate
through the inference path over the SAME weights the ZeRO training path
updates, LoRA adapters are the only trainable params
(``only_optimize_lora``), and the objective is PPO's clipped policy ratio
with a KL penalty against the rollout policy. TPU-native, that is:

    old_logp = actor.token_logprobs(rollouts)        # policy snapshot
    rollout  = actor.generate(prompts)               # LoRA merged in-jit
    update   = actor.train_batch({ppo keys...})      # adapters-only step

The "reward model" is synthetic (prefers even token ids) so the example is
self-contained. A tiny random model + a few iterations only nudges the
reward; the point is the loop mechanics: LoRA-frozen base, PPO objective,
merged-weight generation.

Run: DSTPU_EXAMPLE_SMOKE=1 python examples/rlhf_hybrid.py
"""

import jax
import numpy as np

from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.runtime.hybrid_engine import HybridEngine

actor = HybridEngine({
    "train_batch_size": 8,
    "optimizer": {"type": "adamw", "params": {"lr": 5e-3,
                                              "weight_decay": 0.01}},
    "zero_optimization": {"stage": 2},
    "lora": {"enabled": True, "rank": 4, "alpha": 8.0},
}, build_model(tiny_test(max_seq=64)), eos_token_id=None)

base_snapshot = jax.tree.map(np.asarray,
                             actor.state.master_params["layers"])

rng = np.random.default_rng(0)
prompts = rng.integers(0, 256, (8, 8), dtype=np.int32)
P = prompts.shape[1]


def reward_fn(tokens: np.ndarray) -> np.ndarray:
    """Synthetic preference: fraction of even token ids per rollout."""
    return (tokens % 2 == 0).mean(axis=1)


base = reward_fn(np.asarray(actor.generate(prompts, 16, greedy=True)))
for it in range(8):
    new = np.asarray(actor.generate(prompts, 16, temperature=1.0))
    rollouts = np.concatenate([prompts, new], axis=1).astype(np.int32)
    rewards = reward_fn(new)
    adv = (rewards - rewards.mean()) / (rewards.std() + 1e-6)

    # PPO: snapshot the rollout policy's log-probs, then update against it
    old_logp = np.asarray(actor.token_logprobs(rollouts))
    mask = np.zeros_like(rollouts, np.float32)
    mask[:, P:] = 1.0                      # optimize the generated region
    batch = {"input_ids": rollouts,
             "loss_mask": mask,
             "ppo_old_logp": old_logp,
             "ppo_advantage": adv.astype(np.float32)}
    # several PPO epochs against ONE snapshot: after the first update the
    # ratio departs from 1 and the clip + KL terms engage
    for _ in range(3):
        metrics = actor.train_batch(dict(batch))
    print(f"iter {it}: mean reward {rewards.mean():.3f} "
          f"ppo loss {metrics['loss']:.4f}", flush=True)

final = reward_fn(np.asarray(actor.generate(prompts, 16, greedy=True)))
print(f"greedy reward: before {base.mean():.3f} -> after {final.mean():.3f}")

# the base stayed frozen: every update went through the adapters
after = jax.tree.map(np.asarray, actor.state.master_params["layers"])
drift = max(float(np.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(after),
                            jax.tree.leaves(base_snapshot)))
print(f"frozen-base max drift: {drift:.2e} (adapters-only training)")
assert drift == 0.0
