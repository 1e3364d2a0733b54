"""Import stable-baselines3 PPO checkpoints into this framework.

A reference user's trained artifacts are SB3 ``PPO.save`` zips named
``rl_model_{steps}_steps.zip`` (reference vectorized_env.py:124,
visualize_policy.py:31-35). This module converts them into this
framework's checkpoint format so existing policies carry over: playback
(``visualize_policy.py``), evaluation (``evaluate.py``), and warm-start
fine-tuning (``resume=true``) all work on a converted file.

Format facts (SB3 ``save_to_zip_file``): the zip contains ``data`` (JSON
of constructor args), ``policy.pth`` (a torch ``state_dict``), and
optimizer/system entries. For ``'MlpPolicy'`` (ActorCriticPolicy, the
reference's choice, vectorized_env.py:126) the state_dict keys are::

    log_std                                  (act_dim,)
    mlp_extractor.policy_net.{0,2,...}.weight/.bias   pi hidden layers
    mlp_extractor.value_net.{0,2,...}.weight/.bias    vf hidden layers
    action_net.weight/.bias                  pi head
    value_net.weight/.bias                   vf head
    (pi_/vf_)features_extractor.*            Flatten — parameterless

Mapping to :class:`~marl_distributedformation_tpu.models.MLPActorCritic`
(models/mlp.py — the same two separate tanh MLPs): torch ``Linear`` stores
``weight (out, in)``; flax ``Dense`` stores ``kernel (in, out)`` — every
weight transposes. Only torch's zip/pickle reader is needed, so the
import works without stable_baselines3 installed (it is not in this
image); torch itself is required and the loader fails with a clear error
without it.

Shared-trunk ``net_arch`` variants (``mlp_extractor.shared_net.*``, the
pre-1.6 SB3 default) are rejected explicitly — this framework's MLP is
the separate-networks shape the reference trains.
"""

from __future__ import annotations

import io
import re
import zipfile
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import numpy as np

# Deliberately NO jax import anywhere in this module: conversion is pure
# host-side work (torch unpickle -> numpy -> msgpack), and touching
# jax.numpy would initialize the device backend — a file converter has
# no business taking the chip (one process holds it at a time).

_LINEAR_KEY = re.compile(
    r"^mlp_extractor\.(policy|value)_net\.(\d+)\.(weight|bias)$"
)


def _load_policy_state_dict(path: Path) -> Dict[str, np.ndarray]:
    """Extract ``policy.pth`` from an SB3 zip (or load a bare ``.pth``)
    into plain numpy arrays."""
    try:
        import torch
    except ImportError as e:  # pragma: no cover - torch is in this image
        raise ImportError(
            "sb3_import needs torch to read SB3 .zip/.pth checkpoints"
        ) from e

    # Three on-disk shapes: an SB3 PPO.save zip (has a policy.pth entry),
    # a bare torch state_dict file — which since torch 1.6 is ITSELF a
    # zip (data.pkl + tensor blobs), so zip-ness alone identifies
    # nothing — or a legacy pickle.
    blob = None
    if zipfile.is_zipfile(path):
        with zipfile.ZipFile(path) as zf:
            names = zf.namelist()
            if "policy.pth" in names:
                blob = zf.read("policy.pth")
            elif not any(n.endswith("data.pkl") for n in names):
                raise ValueError(
                    f"{path} is a zip with neither policy.pth (SB3 "
                    f"PPO.save) nor data.pkl (torch state_dict) "
                    f"(entries: {sorted(names)[:8]}...)"
                )
    if blob is not None:
        state = torch.load(
            io.BytesIO(blob), map_location="cpu", weights_only=True
        )
    else:
        state = torch.load(path, map_location="cpu", weights_only=True)
    return {k: v.detach().numpy() for k, v in state.items()}


def sb3_state_dict_to_flax(
    state: Dict[str, np.ndarray],
) -> Tuple[dict, Dict[str, int]]:
    """Map an SB3 ActorCriticPolicy ``state_dict`` onto
    ``MLPActorCritic``'s flax param tree.

    Returns ``({"params": ...}, info)`` where ``info`` records the
    inferred ``obs_dim``, ``act_dim``, and hidden widths.
    """
    if any(k.startswith("mlp_extractor.shared_net") for k in state):
        raise ValueError(
            "SB3 checkpoint uses a shared-trunk net_arch "
            "(mlp_extractor.shared_net.*); only the separate pi/vf "
            "networks of the reference's 'MlpPolicy' are importable"
        )

    # Collect hidden Linear layers per network in module-index order.
    # torch.nn.Sequential interleaves activations, so Linear indices are
    # 0, 2, 4, ... — the sort below restores layer order.
    hidden: Dict[str, Dict[int, Dict[str, np.ndarray]]] = {
        "policy": {},
        "value": {},
    }
    for key, arr in state.items():
        m = _LINEAR_KEY.match(key)
        if m:
            net, idx, part = m.group(1), int(m.group(2)), m.group(3)
            hidden[net].setdefault(idx, {})[part] = arr

    for head in (
        "action_net.weight",
        "action_net.bias",
        "value_net.weight",
        "value_net.bias",
        "log_std",
    ):
        if head not in state:
            raise ValueError(
                f"SB3 checkpoint missing {head!r} — keys: "
                f"{sorted(state)[:12]}..."
            )

    def dense(w: np.ndarray, b: np.ndarray) -> dict:
        return {
            # torch (out, in) -> flax (in, out); ascontiguousarray so the
            # transpose view serializes (msgpack needs C-order buffers)
            "kernel": np.ascontiguousarray(w.T),
            "bias": np.asarray(b),
        }

    params: dict = {}
    widths = []
    for net, prefix in (("policy", "pi"), ("value", "vf")):
        layers = [hidden[net][i] for i in sorted(hidden[net])]
        if not layers:
            raise ValueError(
                f"SB3 checkpoint has no mlp_extractor.{net}_net layers"
            )
        for j, layer in enumerate(layers):
            if "weight" not in layer or "bias" not in layer:
                raise ValueError(
                    f"SB3 checkpoint's mlp_extractor.{net}_net layer {j} "
                    f"is missing {'bias' if 'bias' not in layer else 'weight'}"
                    " — malformed state_dict"
                )
            params[f"{prefix}_{j}"] = dense(layer["weight"], layer["bias"])
        if net == "policy":
            widths = [layer["weight"].shape[0] for layer in layers]
    params["pi_head"] = dense(state["action_net.weight"],
                              state["action_net.bias"])
    params["vf_head"] = dense(state["value_net.weight"],
                              state["value_net.bias"])
    params["log_std"] = np.asarray(state["log_std"]).reshape(-1)

    first_pi = hidden["policy"][min(hidden["policy"])]
    info = {
        "obs_dim": int(first_pi["weight"].shape[1]),
        "act_dim": int(state["action_net.weight"].shape[0]),
        "hidden": tuple(widths),
    }
    return {"params": params}, info


def _steps_from_name(path: Path) -> Optional[int]:
    m = re.search(r"rl_model_(\d+)_steps", path.name)
    return int(m.group(1)) if m else None


def output_path(
    src: Path,
    out_dir: Optional[str | Path] = None,
    num_timesteps: Optional[int] = None,
) -> Path:
    """Where :func:`import_sb3_checkpoint` will write for these inputs."""
    steps = (
        num_timesteps
        if num_timesteps is not None
        else (_steps_from_name(src) or 0)
    )
    base = Path(out_dir) if out_dir is not None else src.parent
    return base / f"rl_model_{steps}_steps.msgpack"


def import_sb3_checkpoint(
    src: str | Path,
    out_dir: Optional[str | Path] = None,
    num_timesteps: Optional[int] = None,
) -> Path:
    """Convert one SB3 ``rl_model_{steps}_steps.zip`` into a framework
    checkpoint next to it (or under ``out_dir``), named so
    ``utils.latest_checkpoint`` discovery finds it.

    The converted file carries policy params only (fresh optimizer state
    on resume — SB3's Adam moments don't map onto optax pytrees, and a
    warm-started fine-tune re-estimates them within a few iterations).

    Single-host warm-start only: multi-host resume goes through
    ``utils.broadcast_restore``, which requires the full learner state
    (opt_state, key) and rejects params-only files loudly. To take an
    imported policy multi-host, fine-tune single-host for one iteration
    first — its save() mints a complete learner checkpoint.
    """
    from flax import serialization

    src = Path(src)
    state = _load_policy_state_dict(src)
    params, info = sb3_state_dict_to_flax(state)
    steps = (
        num_timesteps
        if num_timesteps is not None
        else (_steps_from_name(src) or 0)
    )
    out = output_path(src, out_dir, num_timesteps)
    out.parent.mkdir(parents=True, exist_ok=True)
    target = {
        "policy": "MLPActorCritic",
        "params": params,
        "num_timesteps": steps,
        "sb3_import": {
            "source": src.name,
            "obs_dim": info["obs_dim"],
            "act_dim": info["act_dim"],
            "hidden": list(info["hidden"]),
        },
    }
    out.write_bytes(serialization.msgpack_serialize(target))
    return out


def flax_params_to_sb3_state_dict(params: dict) -> Dict[str, Any]:
    """The reverse mapping: ``MLPActorCritic`` flax params -> a torch
    ``state_dict`` under SB3 ActorCriticPolicy naming.

    Deliberately scoped to the state_dict (a plain ``.pth``), NOT a full
    ``PPO.save`` zip: SB3's ``data`` entry is a version-dependent custom
    serialization we cannot produce faithfully without SB3 installed.
    The state_dict is the stable surface — on the reference stack, load
    with ``model.policy.load_state_dict(torch.load(path))`` after
    constructing ``PPO('MlpPolicy', env, ...)`` as usual. Round-trip
    (export -> import -> identical forward pass) is CI-pinned.
    """
    try:
        import torch
    except ImportError as e:  # pragma: no cover - torch is in this image
        raise ImportError("sb3 export needs torch to write .pth files") from e

    p = params["params"] if "params" in params else params

    def tensor(arr) -> Any:
        # np.array copies: jax/flax leaves surface as READ-ONLY numpy
        # views, which torch.from_numpy warns about (and writing through
        # the tensor would be UB).
        return torch.from_numpy(np.array(arr, dtype=np.float32))

    def linear(name: str) -> Dict[str, Any]:
        return {
            "weight": tensor(np.asarray(p[name]["kernel"]).T),
            "bias": tensor(p[name]["bias"]),
        }

    state: Dict[str, Any] = {"log_std": tensor(p["log_std"])}
    for prefix, net in (("pi", "policy"), ("vf", "value")):
        j = 0
        while f"{prefix}_{j}" in p:
            layer = linear(f"{prefix}_{j}")
            state[f"mlp_extractor.{net}_net.{2 * j}.weight"] = layer["weight"]
            state[f"mlp_extractor.{net}_net.{2 * j}.bias"] = layer["bias"]
            j += 1
        if j == 0:
            raise ValueError(
                f"params carry no {prefix}_0 layer — only MLPActorCritic "
                "checkpoints export to the SB3 MlpPolicy shape"
            )
    head = linear("pi_head")
    state["action_net.weight"], state["action_net.bias"] = (
        head["weight"], head["bias"],
    )
    head = linear("vf_head")
    state["value_net.weight"], state["value_net.bias"] = (
        head["weight"], head["bias"],
    )
    return state


def export_sb3_state_dict(
    src: str | Path, out: Optional[str | Path] = None
) -> Path:
    """Export a framework checkpoint's policy to ``{stem}.sb3.pth``."""
    import torch
    from flax import serialization

    src = Path(src)
    from marl_distributedformation_tpu.utils.checkpoint import (
        msgpack_restore_file,
    )

    # quarantine=False: ``src`` is a CALLER-supplied file, not a
    # trainer-owned checkpoint directory — a read-only conversion must
    # never rename a user's input aside, just fail loudly.
    raw = msgpack_restore_file(src, quarantine=False)
    policy = raw.get("policy", "MLPActorCritic")
    if policy != "MLPActorCritic":
        raise ValueError(
            f"checkpoint policy {policy!r} has no SB3 equivalent; only "
            "MLPActorCritic maps onto 'MlpPolicy'"
        )
    state = flax_params_to_sb3_state_dict(raw["params"])
    out = Path(out) if out is not None else src.with_suffix(".sb3.pth")
    out.parent.mkdir(parents=True, exist_ok=True)
    torch.save(state, out)
    return out


def main(argv: Optional[list] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="Convert SB3 PPO checkpoints (rl_model_*_steps.zip) "
        "to framework checkpoints for playback/eval/fine-tuning — or, "
        "with --export, framework checkpoints back to torch state_dicts "
        "under SB3 MlpPolicy naming."
    )
    ap.add_argument("src", nargs="+", help="SB3 .zip (or bare policy "
                    ".pth); with --export: framework .msgpack checkpoints")
    ap.add_argument("--out-dir", default=None, help="output directory "
                    "(default: next to each source file)")
    ap.add_argument("--steps", type=int, default=None,
                    help="override num_timesteps (default: parsed from "
                    "the rl_model_{steps}_steps filename)")
    ap.add_argument("--export", action="store_true",
                    help="reverse direction: framework checkpoint -> "
                    "{stem}.sb3.pth torch state_dict (load on the "
                    "reference stack via policy.load_state_dict)")
    args = ap.parse_args(argv)
    if args.export:
        if args.steps is not None:
            ap.error("--steps does not apply to --export")
        # Same pre-write collision guard as the import path: two sources
        # with one stem under --out-dir must not silently clobber.
        planned_out: Dict[Path, str] = {}
        for src in args.src:
            dest = (
                Path(args.out_dir) / (Path(src).stem + ".sb3.pth")
                if args.out_dir is not None
                else Path(src).with_suffix(".sb3.pth")
            )
            if dest in planned_out:
                ap.error(
                    f"output collision: {src} and {planned_out[dest]} "
                    f"both map to {dest}"
                )
            planned_out[dest] = src
        for dest, src in planned_out.items():
            out = export_sb3_state_dict(src, dest)
            print(f"{src} -> {out}")
        return
    if args.steps is not None and len(args.src) > 1:
        ap.error("--steps with multiple sources would write every input "
                 "to the same rl_model_{steps}_steps.msgpack")
    # Detect output collisions BEFORE any write (two sources with the same
    # step count under one --out-dir would silently clobber each other).
    planned: Dict[Path, str] = {}
    for src in args.src:
        out = output_path(Path(src), args.out_dir, args.steps)
        if out in planned:
            ap.error(
                f"output collision: {src} and {planned[out]} both map to "
                f"{out} — pass distinct --out-dir per run"
            )
        planned[out] = src
    for out, src in planned.items():
        import_sb3_checkpoint(src, args.out_dir, args.steps)
        print(f"{src} -> {out}")


if __name__ == "__main__":
    main()
