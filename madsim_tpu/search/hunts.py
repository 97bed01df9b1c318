"""The canonical guided hunts: shared by `make fuzz-demo`, `make
actorc-demo` and the acceptance gates (tests/test_search.py).

Both hunts compare coverage-guided search against the MATCHED random-
mutation baseline (``SearchConfig(guided=False)``: same operators, same
rates, same budget — no feedback), the comparison the ROADMAP item-2
gate asks for:

- **pair** — the synthetic conjunction family (search/family.py): the
  bug needs two specific node restarts the template never performs, and
  partial progress is behaviorally visible. Guided reaches it in ~73
  seeds where random needs ~409 (measured; docs/search.md "when guided
  beats random") — the seeds-to-bug gate.
- **raft** — a seeded double-vote bug (RaftDeviceConfig
  ``buggy_double_vote``) made schedule-gated: a WIDE election window
  plus narrow network latency makes natural candidate collisions rare
  (~0.8%/seed), while overlapping long PAUSEs flush buffered election
  timers simultaneously on resume — synchronized elections, reliable
  collisions (measured 36/512 under a hand-built sync schedule vs
  4/512 fault-free). The template's short, disjoint pauses are benign;
  the search must grow overlap through time jitter and recombination.
  Guided finds ~2x the failing seeds of random at the same budget —
  the bugs-at-budget gate (first-bug ties are expected here: both modes
  share generation-1 children by construction, and the residual
  seed-dependent collision floor is reachable by either).

- **paxos** — the first actorc-compiled DSL-only family
  (docs/actorc.md): multi-decree Paxos with forgetful acceptors
  (``PaxosConfig(buggy_forgetful_acceptor=True)`` flips ONE
  ``durable`` annotation — the textbook stable-storage violation).
  Every decree is contended, so each opens a ~20 ms amnesia window
  between the first proposer's accept-quorum and the rival's
  promise-quorum; the consistency violation needs TWO restarts
  jittered from the benign early template into a window (one
  in-window restart violates ~1%/seed, two up to ~7%), while one
  in-window restart already perturbs rounds visibly — the staircase.
  Measured: guided reaches the conflict at seed ~191 where random
  finds nothing in 512 (``make actorc-demo``).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from .config import SearchConfig
from .family import (
    HUNT_NODES,
    HUNT_ROWS,
    GuidedPairActor,
    GuidedPairConfig,
    engine_config,
    family_schedule,
    hunt_search_config,
)


class Hunt(NamedTuple):
    """One demo/test hunt setup: build engines with
    ``DeviceEngine(actor, cfg)`` and sweep with ``template`` +
    ``search(guided=...)``."""

    name: str
    actor: object
    cfg: object
    template: np.ndarray
    search: object            # callable(guided: bool) -> SearchConfig
    sweep_kw: dict            # canonical sweep knobs (batch, chunks, ...)


def pair_hunt() -> Hunt:
    """The conjunction family at the canonical shape."""
    acfg = GuidedPairConfig(n=HUNT_NODES)
    return Hunt(
        name="pair_restart_family",
        actor=GuidedPairActor(acfg),
        cfg=engine_config(acfg),
        template=family_schedule(HUNT_ROWS, acfg),
        search=hunt_search_config,
        sweep_kw=dict(recycle=True, batch_worlds=32, chunk_steps=32,
                      max_steps=50_000_000),
    )


def raft_hunt() -> Hunt:
    """The seeded raft double-vote bug, schedule-gated (see module
    docstring for why each constant is what it is)."""
    from ..engine import EngineConfig, RaftActor, RaftDeviceConfig
    from ..engine.core import FAULT_PAUSE, FAULT_RESUME

    rcfg = RaftDeviceConfig(n=5, buggy_double_vote=True,
                            elect_min_us=150_000, elect_max_us=1_300_000,
                            heartbeat_us=40_000)
    cfg = EngineConfig(n_nodes=5, outbox_cap=6, queue_cap=64,
                       t_limit_us=1_600_000, latency_min_us=1_000,
                       latency_max_us=3_000, metrics=True)
    # Benign template: three short, disjoint single-node pauses.
    template = np.array([
        [200_000, FAULT_PAUSE, 4, 0],
        [240_000, FAULT_RESUME, 4, 0],
        [500_000, FAULT_PAUSE, 3, 0],
        [540_000, FAULT_RESUME, 3, 0],
        [800_000, FAULT_PAUSE, 4, 0],
        [840_000, FAULT_RESUME, 4, 0]], np.int32)

    def search(guided: bool = True) -> SearchConfig:
        return SearchConfig(corpus=16, guided=guided, splice_pct=20,
                            disable_pct=5, time_pct=40, node_pct=15,
                            op_pct=5, time_jitter_us=400_000)

    return Hunt(
        name="seeded_raft_double_vote",
        actor=RaftActor(rcfg),
        cfg=cfg,
        template=template,
        search=search,
        sweep_kw=dict(recycle=True, batch_worlds=32, chunk_steps=64,
                      max_steps=50_000_000),
    )


def paxos_hunt() -> Hunt:
    """The multi-decree Paxos forgetful-acceptor hunt — the first
    DSL-only family leg (see module docstring for the staircase
    shape; tuning measured in actorc/families/paxos.py)."""
    from ..actorc.families.paxos import (PaxosActor, PaxosConfig,
                                         engine_config, hunt_template)

    xcfg = PaxosConfig(buggy_forgetful_acceptor=True, contend_all=True)

    def search(guided: bool = True) -> SearchConfig:
        return SearchConfig(corpus=32, guided=guided, splice_pct=20,
                            disable_pct=5, time_pct=40, node_pct=15,
                            op_pct=5, time_jitter_us=60_000)

    return Hunt(
        name="paxos_forgetful_acceptor",
        actor=PaxosActor(xcfg),
        cfg=engine_config(xcfg, metrics=True),
        template=hunt_template(xcfg),
        search=search,
        sweep_kw=dict(recycle=True, batch_worlds=32, chunk_steps=32,
                      max_steps=50_000_000),
    )
