"""Synthetic guided-hunt family: a conjunction bug with observable progress.

``GuidedPairActor`` is the pair-restart family the fuzzer-loop gates run
on (ISSUE/ROADMAP item 2): the invariant fires iff BOTH target nodes
have been restarted at least once — like triage's
:class:`~madsim_tpu.triage.synthetic.PairRestartActor`, but with the one
property that makes coverage guidance *matter*: partial progress is
behaviorally visible. The first restart of each target emits a
"progress beacon" message, so a world that restarted one target delivers
a different ``kind_hist`` than a world that restarted none — they land
in different behavior-signature buckets (obs/coverage.py), the guided
corpus keeps the one-target schedule as a parent, and one more node
rotation reaches the conjunction. A random-mutation baseline must hit
both targets in a single mutation pass of the original template — the
classic staircase argument for why coverage-guided search beats random
fuzzing on conjunctive bugs (docs/search.md "when guided beats
random"), here with an exactly measurable seeds-to-bug gap
(``make fuzz-demo``, tests/test_search.py).

The template schedule (:func:`family_schedule`) restarts only filler
nodes: the bug is reachable EXCLUSIVELY through the search's node-
rotation operator, never by seed enumeration — a fixed-schedule sweep
can run forever without finding it.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ..engine.core import FAULT_RESTART, EngineConfig, Outbox
from ..engine.lanes import take_small, upd
from ..engine.queue import Event


@dataclasses.dataclass(frozen=True)
class GuidedPairConfig:
    """Static parameters of the guided pair-restart family."""

    n: int = 8        # nodes per world (engine n_nodes must match);
                      # more filler nodes = a harder random baseline
    node_a: int = 1   # the invariant fires when BOTH targets have
    node_b: int = 2   # been restarted at least once


class GuidedPairActor:
    """Pair-restart conjunction with progress beacons.

    Event kinds: 0 = the seed message (keeps an empty-schedule world
    alive for one delivered step), 1 = a progress beacon — sent exactly
    once per target node, on its first restart. Beacons are ordinary
    messages (latency-sampled, loss/clog/dead-dst rules apply), so their
    delivery counts land in the MetricsBlock ``kind_hist`` like any
    actor traffic and the behavior signature separates
    zero/one/two-target worlds with no search-specific plumbing.
    """

    num_kinds = 2
    kind_names = ["seed", "progress"]
    invariant_id = "guided_pair_conjunction"

    def __init__(self, acfg: GuidedPairConfig = GuidedPairConfig()):
        self.acfg = acfg

    def init(self, cfg: EngineConfig, rng):
        s = {"restarts": jnp.zeros((cfg.n_nodes,), jnp.int32)}
        evs = [Event.make(time=1, kind=0,
                          payload_words=cfg.payload_words)]
        return s, evs, rng

    def handle(self, cfg, s, ev, now, rng):
        return s, Outbox.empty(cfg), rng, jnp.asarray(False)

    def on_restart(self, cfg, s, node, now, rng):
        prev = take_small(s["restarts"], node)
        restarts = upd(s["restarts"], node, prev + 1)
        a, b = self.acfg.node_a, self.acfg.node_b
        # First restart of a TARGET node beacons once: the observable
        # progress edge the novelty signal keys on.
        beacon = ((node == a) | (node == b)) & (prev == 0)
        ob = Outbox.empty(cfg)
        ob = ob._replace(
            valid=ob.valid.at[0].set(beacon),
            kind=ob.kind.at[0].set(jnp.int32(1)),
            dst=ob.dst.at[0].set(jnp.int32(0)))
        return {"restarts": restarts}, ob, rng

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


def family_schedule(n_rows: int = 8,
                    acfg: GuidedPairConfig = GuidedPairConfig(),
                    t0_us: int = 20_000, dt_us: int = 20_000) -> np.ndarray:
    """The ``(n_rows, 4)`` template: restarts of FILLER nodes only, at
    strictly increasing times. No subset of the template fails — the
    bug is reachable only through the search's mutation operators."""
    fillers = [i for i in range(acfg.n)
               if i not in (acfg.node_a, acfg.node_b)]
    if not fillers:
        raise ValueError("GuidedPairConfig needs at least one filler node")
    rows = np.zeros((n_rows, 4), np.int32)
    rows[:, 0] = t0_us + dt_us * np.arange(n_rows)
    rows[:, 1] = FAULT_RESTART
    rows[:, 2] = [fillers[i % len(fillers)] for i in range(n_rows)]
    return rows


def engine_config(acfg: GuidedPairConfig = GuidedPairConfig()
                  ) -> EngineConfig:
    """The canonical metrics-on engine config for this family (metrics
    are required: the novelty signal hashes the MetricsBlock)."""
    return EngineConfig(n_nodes=acfg.n, outbox_cap=2, queue_cap=64,
                        t_limit_us=2_000_000, metrics=True)


# The canonical guided-hunt shape shared by `make fuzz-demo` and
# tests/test_search.py: 12 nodes (10 fillers) and a
# 6-row template make a single-pass double-target hit rare — measured
# seeds-to-bug ~73 guided vs ~409 random under HUNT_SEARCH, the
# staircase gap the acceptance gate asserts.
HUNT_NODES = 12
HUNT_ROWS = 6


def hunt_search_config(guided: bool = True, corpus: int = 32):
    """The tuned :class:`~madsim_tpu.search.SearchConfig` of the
    canonical family hunt; ``guided=False`` is the matched
    random-mutation baseline (same operators and rates, no feedback)."""
    from .config import SearchConfig

    return SearchConfig(corpus=corpus, guided=guided, splice_pct=20,
                        disable_pct=5, time_pct=20, node_pct=15,
                        op_pct=5)
