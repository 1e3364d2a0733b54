"""Contract tests for the reference-verbatim entry shim (vectorized_env.py).

The migration guide claims ``python vectorized_env.py name=x`` and
``FormationEnv(cfg)`` work unchanged (reference README.md:18,
vectorized_env.py:17); these pin that claim the way test_cli_dispatch pins
train.py's.
"""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import train as train_cli
import vectorized_env as shim
from marl_distributedformation_tpu.compat.vec_env import FormationVecEnv
from marl_distributedformation_tpu.utils import load_config


def test_shim_forwards_to_train_main():
    assert shim.main is train_cli.main


def test_shim_import_is_light():
    """Importing the shim for FormationEnv must not pull the training
    stack (the lazy-main contract)."""
    import subprocess

    code = (
        "import vectorized_env, sys; "
        "assert 'train' not in sys.modules, 'train imported eagerly'; "
        "assert 'marl_distributedformation_tpu.algo' not in sys.modules"
    )
    subprocess.run(
        [sys.executable, "-c", code],
        check=True,
        cwd=Path(__file__).resolve().parent.parent,
    )


def test_reference_signature_formation_env_constructs_and_steps():
    cfg = load_config(["name=shimtest", "num_formation=4", "platform=cpu"])
    env = shim.FormationEnv(cfg)
    assert isinstance(env, FormationVecEnv)
    assert env.num_envs == 4 * cfg.num_agents_per_formation
    obs = env.reset()
    obs2, rewards, dones, infos = env.step(np.zeros((env.num_envs, 2)))
    assert obs.shape == obs2.shape == (env.num_envs, obs.shape[1])
    assert rewards.shape == dones.shape == (env.num_envs,)
    assert len(infos) == env.num_envs


def test_shim_trains_and_snapshots_config(tmp_path):
    """The documented verbatim command trains end-to-end and leaves the
    hydra-snapshot analog (config.json); a resume does not clobber it.
    (``log_dir=`` keeps the run out of the checkout's ``logs/``.)"""
    run_dir = tmp_path / "logs" / "shimrun"
    args = [
        "name=shimrun", "platform=cpu", "num_formation=4",
        "num_agents_per_formation=3", "total_timesteps=120", "n_steps=10",
        "save_freq=10", "use_wandb=false", f"log_dir={run_dir}",
    ]
    shim.main(args)
    assert (run_dir / "config.json").exists()
    assert list(run_dir.glob("rl_model_*_steps.msgpack"))
    before = (run_dir / "config.json").read_text()
    shim.main(args + ["resume=true", "total_timesteps=240"])
    assert (run_dir / "config.json").read_text() == before
    assert (run_dir / "config_resume.json").exists()

    # A resume NEVER writes the canonical snapshot — even when it is
    # missing (pre-feature run), so config.json can't claim resume cfg
    # was the original training config.
    (run_dir / "config.json").unlink()
    shim.main(args + ["resume=true", "total_timesteps=360"])
    assert not (run_dir / "config.json").exists()


def test_ppo_from_config_null_schedule_knobs():
    """Explicit null overrides of the optional schedule knobs must parse
    as 'off', not crash (log_std_decay_start=null used to hit
    float(None))."""
    cfg = load_config(
        [
            "name=x",
            "ent_coef_final=null",
            "log_std_final=null",
            "log_std_decay_start=null",
        ]
    )
    ppo = train_cli.ppo_from_config(cfg)
    assert ppo.ent_coef_final is None
    assert ppo.log_std_final is None
    assert ppo.log_std_decay_start == 0.0


def test_ppo_from_config_schedule_knobs_forwarded():
    cfg = load_config(
        ["name=x", "log_std_final=-2.5", "log_std_decay_start=0.5"]
    )
    ppo = train_cli.ppo_from_config(cfg)
    assert ppo.log_std_final == -2.5
    assert ppo.log_std_decay_start == 0.5


def test_hidden_sizes_knob():
    """hidden_sizes=[...] (the SB3 policy_kwargs/net_arch analog) reaches
    the constructed model; null keeps the reference 'MlpPolicy' default."""
    cfg = load_config(
        ["name=x", "hidden_sizes=[128,128]", "num_formation=4",
         "num_agents_per_formation=3"]
    )
    trainer = train_cli.build_trainer(cfg)
    assert tuple(trainer.model.hidden) == (128, 128)
    cfg2 = load_config(
        ["name=x", "num_formation=4", "num_agents_per_formation=3"]
    )
    assert tuple(train_cli.build_trainer(cfg2).model.hidden) == (64, 64)
