"""Backend bit-exactness check: the same seeds must trace identically on
every XLA backend.

This is the device-engine analog of the host determinism checker
(`madsim/src/sim/rand.rs:84-107` / `runtime/mod.rs:164-189`): the engine
contract (engine/core.py docstring) says (seed, config) ⇒ bit-exact
trajectories, *re-runnable anywhere*. Everything in the step function is
integer or exactly-representable f32 arithmetic, so TPU and CPU must agree
to the last bit — any divergence is an engine bug (e.g. a reduction order
leak or a fast-math rewrite), not noise. chip_smoke.py runs this on the
chip against the CPU backend (phase A).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

from .core import DeviceEngine


def run_on(eng: DeviceEngine, device, seeds, faults=None, max_steps: int = 100_000):
    """init + run a seed batch with all arrays placed on ``device``."""
    with jax.default_device(device):
        state = eng.init(np.asarray(seeds), faults=faults)
        state = eng.run(state, max_steps=max_steps)
        jax.block_until_ready(state)
    return jax.tree.map(np.asarray, state)


def crosscheck_backends(eng: DeviceEngine, seeds, faults=None,
                        max_steps: int = 100_000,
                        device_a=None, device_b=None) -> Dict[str, int]:
    """Run the same batch on two backends and assert leafwise bit-equality.

    Defaults: device_a = the default backend (TPU when present),
    device_b = host CPU. Returns a small summary dict; raises AssertionError
    with the first differing leaf on any mismatch.
    """
    device_a = device_a if device_a is not None else jax.devices()[0]
    device_b = device_b if device_b is not None else jax.devices("cpu")[0]

    state_a = run_on(eng, device_a, seeds, faults, max_steps)
    state_b = run_on(eng, device_b, seeds, faults, max_steps)

    leaves_a, treedef_a = jax.tree.flatten(state_a)
    leaves_b, treedef_b = jax.tree.flatten(state_b)
    assert treedef_a == treedef_b
    mismatched = []
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(state_a)[0]]
    for path, a, b in zip(paths, leaves_a, leaves_b):
        if a.dtype != b.dtype or a.shape != b.shape or not np.array_equal(a, b):
            diffs = int(np.sum(a != b)) if a.shape == b.shape else -1
            mismatched.append(f"{path}: {diffs} differing elements "
                              f"({a.dtype}{list(a.shape)})")
    assert not mismatched, (
        f"{device_a.platform} vs {device_b.platform} trajectories diverged "
        f"on {len(mismatched)} leaves:\n  " + "\n  ".join(mismatched[:10]))

    obs_a = {k: np.asarray(v) for k, v in eng.observe(state_a).items()}
    obs_b = {k: np.asarray(v) for k, v in eng.observe(state_b).items()}
    for k in obs_a:
        assert np.array_equal(obs_a[k], obs_b[k]), f"observe[{k}] diverged"

    return {
        "n_worlds": int(np.asarray(seeds).shape[0]),
        "n_leaves": len(leaves_a),
        "platform_a": device_a.platform,
        "platform_b": device_b.platform,
        "bitwise_equal": 1,
    }
