"""Synthetic minimal-repro actor: a bug with a KNOWN minimal schedule.

``PairRestartActor`` raises its invariant iff BOTH of two designated
nodes (``node_a``, ``node_b``) have been restarted at least once — a
conjunction over fault-schedule rows, so a schedule's minimal failing
subset is exactly {the one row restarting ``node_a``, the one row
restarting ``node_b``} when every other row restarts filler nodes.

That known answer is what makes it the triage test fixture and the
``make triage-demo`` workload: the batched ddmin loop
(triage/minimize.py) must converge to exactly those two rows,
bitwise-identically across runs and across the serial/pipelined sweep
paths, and the 1-minimality verification has ground truth to be
checked against.

It is also registered in the replay registry (obs/cli.py, actor name
``pair_restart``), so minimized repro bundles emitted by the corpus
replay end to end through ``python -m madsim_tpu.obs replay``.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from ..engine.core import FAULT_RESTART, EngineConfig, Outbox
from ..engine.lanes import take_small, upd
from ..engine.queue import Event


@dataclasses.dataclass(frozen=True)
class PairRestartConfig:
    """Static parameters of the synthetic pair-restart bug."""

    n: int = 4        # nodes per world (engine n_nodes must match)
    node_a: int = 1   # the invariant fires when BOTH of these nodes
    node_b: int = 2   # have been restarted at least once


class PairRestartActor:
    """Counts per-node restarts; the bug is ``restarts[a] & restarts[b]``.

    Deliberately minimal: one seed message keeps the world alive for a
    first delivered step, every fault row in the schedule is an engine-
    level ``FAULT_RESTART`` whose ``on_restart`` hook bumps the counter,
    and the invariant is a pure conjunction over the counter lane — no
    timing, no randomness, so the failure depends ONLY on which schedule
    rows are enabled (the property the ddmin convergence tests pin).
    """

    num_kinds = 1
    invariant_id = "pair_restart_conjunction"

    def __init__(self, acfg: PairRestartConfig = PairRestartConfig()):
        self.acfg = acfg

    def init(self, cfg: EngineConfig, rng):
        s = {"restarts": jnp.zeros((cfg.n_nodes,), jnp.int32)}
        # One seed message so even an empty-schedule world delivers a
        # step (and the world's step/delivery observations are nonzero).
        evs = [Event.make(time=1, kind=0,
                          payload_words=cfg.payload_words)]
        return s, evs, rng

    def handle(self, cfg, s, ev, now, rng):
        return s, Outbox.empty(cfg), rng, jnp.asarray(False)

    def on_restart(self, cfg, s, node, now, rng):
        restarts = upd(s["restarts"], node,
                       take_small(s["restarts"], node) + 1)
        return {"restarts": restarts}, Outbox.empty(cfg), rng

    def invariant(self, cfg, s):
        a, b = self.acfg.node_a, self.acfg.node_b
        return (s["restarts"][..., a] > 0) & (s["restarts"][..., b] > 0)

    def observe(self, cfg, s):
        a, b = self.acfg.node_a, self.acfg.node_b
        return {
            "restarts_a": s["restarts"][..., a],
            "restarts_b": s["restarts"][..., b],
            # dtype-pinned sum: a bare jnp.sum widens to i64 under the
            # x64 flag (tracelint TRC003).
            "restarts_total": jnp.sum(s["restarts"], axis=-1,
                                      dtype=jnp.int32),
        }


def pair_schedule(n_rows: int = 32, need: Tuple[int, int] = (5, 20),
                  acfg: PairRestartConfig = PairRestartConfig(),
                  filler_node: int = 0, t0_us: int = 10_000,
                  dt_us: int = 10_000) -> np.ndarray:
    """A ``(n_rows, 4)`` restart schedule whose minimal failing subset
    is exactly rows ``need``: row ``need[0]`` restarts ``node_a``, row
    ``need[1]`` restarts ``node_b``, every other row restarts
    ``filler_node`` (times strictly increasing, so rows are distinct)."""
    i, j = need
    if not (0 <= i < n_rows and 0 <= j < n_rows and i != j):
        raise ValueError(f"need rows must be two distinct indices in "
                         f"[0, {n_rows}); got {need}")
    rows = np.zeros((n_rows, 4), np.int32)
    rows[:, 0] = t0_us + dt_us * np.arange(n_rows)
    rows[:, 1] = FAULT_RESTART
    rows[:, 2] = filler_node
    rows[i, 2] = acfg.node_a
    rows[j, 2] = acfg.node_b
    return rows


def engine_config(acfg: PairRestartConfig = PairRestartConfig(),
                  metrics: bool = False) -> EngineConfig:
    """The canonical engine config for this actor (small queue — the
    schedule is the only event source beyond the seed message)."""
    return EngineConfig(n_nodes=acfg.n, outbox_cap=2, queue_cap=64,
                        t_limit_us=2_000_000, metrics=metrics)
