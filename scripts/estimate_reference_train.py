#!/usr/bin/env python
"""Estimate the reference's FULL SB3-PPO training throughput on this CPU.

BENCH vs_baseline honesty (VERDICT.md r2 weak #4): comparing our full
training iteration against the reference's *env-stepping-only* 1,066
formation-steps/s flatters the reference-relative speedup the wrong way —
reference training also pays policy inference and the SB3 minibatch update.
SB3 itself is not installable in this image, so this script MEASURES the
three components the SB3 on-policy loop executes (collect_rollouts +
train; SURVEY.md §3.1) with the same torch CPU stack the reference uses:

1. env stepping: the measured 1,066 formation-steps/s (BASELINE.md,
   M=1000 x N=5 replica of vectorized_env.py:71-81) -> 1.066 vec-steps/s;
2. policy inference: MlpPolicy actor-critic forward (2x64 tanh trunk,
   value head, Gaussian sample — SB3 default architecture) on the
   (M*N, 8) observation batch, once per vec-step;
3. PPO update: per rollout of n_steps=10 vec-steps, 10 epochs x
   ceil(500_000/64)... precisely: total = n_steps*M*N = 50_000
   agent-transitions, minibatch 64 -> 781 full minibatches per epoch,
   10 epochs (SB3 defaults; vectorized_env.py:126-137) of
   forward+backward+Adam on the same architecture.

Result: formation-steps/s for the full loop =
    (n_steps * M) / (n_steps * (t_env_vecstep + t_infer) + t_update)

Run: python scripts/estimate_reference_train.py
The output feeds docs/reference_train_estimate.md.
"""

from __future__ import annotations

import json
import time

import torch
import torch.nn as nn

M, N, OBS, ACT = 1000, 5, 8, 2
N_STEPS, EPOCHS, MB = 10, 10, 64
ENV_VEC_STEPS_PER_SEC = 1.07  # BASELINE.md measured, M=1000 x N=5


class MlpPolicy(nn.Module):
    """SB3 'MlpPolicy' default shape: separate 2x64-tanh actor and critic
    trunks, Gaussian head with state-independent log_std."""

    def __init__(self) -> None:
        super().__init__()
        self.actor = nn.Sequential(
            nn.Linear(OBS, 64), nn.Tanh(), nn.Linear(64, 64), nn.Tanh()
        )
        self.critic = nn.Sequential(
            nn.Linear(OBS, 64), nn.Tanh(), nn.Linear(64, 64), nn.Tanh()
        )
        self.mu = nn.Linear(64, ACT)
        self.v = nn.Linear(64, 1)
        self.log_std = nn.Parameter(torch.zeros(ACT))

    def forward(self, obs):
        a = self.actor(obs)
        c = self.critic(obs)
        return self.mu(a), self.log_std, self.v(c)


def timeit(fn, min_s=2.0):
    fn()  # warmup
    n, t0 = 0, time.perf_counter()
    while True:
        fn()
        n += 1
        dt = time.perf_counter() - t0
        if dt > min_s:
            return dt / n


def main() -> None:
    torch.set_num_threads(1)  # the reference runs single-process CPU
    policy = MlpPolicy()
    opt = torch.optim.Adam(policy.parameters(), lr=1e-3, eps=1e-5)

    obs_batch = torch.rand(M * N, OBS)

    def infer():
        with torch.no_grad():
            mu, log_std, v = policy(obs_batch)
            actions = mu + log_std.exp() * torch.randn_like(mu)
            # log-prob, as SB3 computes during collection
            ((actions - mu) ** 2).sum(-1)

    t_infer = timeit(infer)

    mb_obs = torch.rand(MB, OBS)
    mb_act = torch.rand(MB, ACT)
    mb_adv = torch.rand(MB)
    mb_ret = torch.rand(MB)
    mb_olp = torch.rand(MB)

    def minibatch():
        mu, log_std, v = policy(mb_obs)
        lp = (
            -0.5 * (((mb_act - mu) / log_std.exp()) ** 2).sum(-1)
            - log_std.sum()
        )
        ratio = (lp - mb_olp).exp()
        adv = (mb_adv - mb_adv.mean()) / (mb_adv.std() + 1e-8)
        pl = -torch.min(
            adv * ratio, adv * ratio.clamp(0.8, 1.2)
        ).mean()
        vl = ((mb_ret - v.squeeze(-1)) ** 2).mean()
        loss = pl + 0.5 * vl + 0.01 * log_std.sum()
        opt.zero_grad()
        loss.backward()
        nn.utils.clip_grad_norm_(policy.parameters(), 0.5)
        opt.step()

    t_mb = timeit(minibatch)

    total_transitions = N_STEPS * M * N
    n_minibatches = EPOCHS * (total_transitions // MB)
    t_env_vecstep = 1.0 / ENV_VEC_STEPS_PER_SEC
    t_rollout = N_STEPS * (t_env_vecstep + t_infer)
    t_update = n_minibatches * t_mb
    t_iteration = t_rollout + t_update
    rate = N_STEPS * M / t_iteration

    out = {
        "t_infer_per_vecstep_s": round(t_infer, 5),
        "t_minibatch_s": round(t_mb, 6),
        "n_minibatches_per_iteration": n_minibatches,
        "t_env_per_vecstep_s": round(t_env_vecstep, 4),
        "t_rollout_s": round(t_rollout, 3),
        "t_update_s": round(t_update, 3),
        "t_iteration_s": round(t_iteration, 3),
        "reference_train_formation_steps_per_sec": round(rate, 1),
        "env_only_formation_steps_per_sec": ENV_VEC_STEPS_PER_SEC * M,
        "config": {
            "M": M, "N": N, "n_steps": N_STEPS, "epochs": EPOCHS,
            "minibatch": MB, "torch_threads": 1,
        },
    }
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
