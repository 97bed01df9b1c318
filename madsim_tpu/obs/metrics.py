"""Device-resident sweep metrics: the always-on counter layer.

``MetricsBlock`` is a per-world pytree of int32 counters that rides in
``WorldState.metrics`` when ``EngineConfig(metrics=True)`` — a *separate
leaf* the step updates but never reads for simulation decisions, so:

- **bitwise invisibility**: a metrics-on run walks the bit-identical
  trajectory of a metrics-off run (no RNG draw, queue write, or actor
  input ever depends on a counter) — tier-1-gated for raft/pb/tpc across
  plain/recycled/pipelined sweeps in tests/test_obs.py;
- **zero cost when off**: with ``metrics=False`` the field is ``None``
  (an empty pytree subtree), the update code is not even traced, and the
  compiled step is the exact pre-existing program — the PR 3 per-step
  op budget in tests/test_queue_insert.py holds unchanged.

The counters survive world recycling for free: they live in the world
slot, the sweep's slot→seed index attributes them per seed at
retirement, and ``SweepResult.metrics`` reports per-seed frames plus the
fleet aggregate. The bridge kernel carries the analogous
block for host-workload sweeps (``bridge/kernel.py`` ``BridgeMetrics``).

This module deliberately imports nothing from :mod:`madsim_tpu.engine`
(the engine imports *it*); the fault-kind count mirrors the
``FAULT_KILL..FAULT_RESUME`` op range in engine/core.py and is asserted
against it in tests/test_obs.py.

Packed-lane interplay (engine/lanes.py, docs/perf.md "Roofline
round 2"): the counters stay **int32 in both dtype profiles** — they
are unbounded counts (the registry's wide ``counter`` category), not
value-bounded lanes — while the narrow code lanes feed them only
through the engine's widened in-flight values (``ev.kind`` is i32 by
the time it indexes ``kind_hist``/``fault_hist``). That keeps the
``m_*`` observations bit-identical between ``packed=True`` and the i32
reference profile, which the packed crosscheck matrix in
tests/test_obs.py relies on.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

# Width of the fault-injection histogram: one bin per FAULT_* op
# (engine/core.py FAULT_KILL=0 .. FAULT_RESUME=9).
NUM_FAULT_KINDS = 10

# Observation-dict prefix for metrics fields (DeviceEngine.observe adds
# one ``m_<field>`` entry per block field when metrics are on).
OBS_PREFIX = "m_"


class MetricsBlock(NamedTuple):
    """Per-world simulation counters (leading world axis when batched).

    Counter semantics (all int32; increments are masked on the world's
    pre-step ``active`` flag, so a frozen world's block never moves):

    - ``msgs_sent``: non-timer outbox rows a live handler offered to the
      network (send *attempts*, before loss/clog).
    - ``msgs_delivered`` / ``timer_fires``: events actually handled by
      the actor, split message vs (generation-valid) timer.
    - ``drop_loss``: sends dropped at send time — Bernoulli loss or a
      clogged node/link (`net/network.rs:249-257` sampling point).
    - ``drop_stale`` / ``drop_dead``: popped events discarded because
      the timer's node generation changed (kill/restart) or the
      destination was dead at delivery time.
    - ``drop_out_of_time``: events popped at/past ``t_limit_us``.
    - ``enqueued``: events inserted into the queue (actor sends, timer
      arms, fault rows); ``drop_overflow`` counts inserts refused by a
      full queue, ``drop_inf`` deadline-saturated events dropped at
      push (queue.py INF_TIME contract).
    - ``vtime_us``: virtual microseconds this world advanced (the sum
      of per-step clock deltas; equals the world's final clock).
    - ``fault_hist``: (NUM_FAULT_KINDS,) injections applied, by op.
    - ``kind_hist``: (num_kinds,) delivered events by actor event kind
      (the actor's ``kind_names`` order).
    """

    msgs_sent: jnp.ndarray
    msgs_delivered: jnp.ndarray
    timer_fires: jnp.ndarray
    drop_loss: jnp.ndarray
    drop_stale: jnp.ndarray
    drop_dead: jnp.ndarray
    drop_out_of_time: jnp.ndarray
    enqueued: jnp.ndarray
    drop_overflow: jnp.ndarray
    drop_inf: jnp.ndarray
    vtime_us: jnp.ndarray
    fault_hist: jnp.ndarray   # (NUM_FAULT_KINDS,)
    kind_hist: jnp.ndarray    # (num_kinds,)

    @staticmethod
    def zeros(num_kinds: int) -> "MetricsBlock":
        """A fresh (single-world) block for an actor with ``num_kinds``
        event kinds."""
        z = jnp.int32(0)
        return MetricsBlock(
            msgs_sent=z, msgs_delivered=z, timer_fires=z, drop_loss=z,
            drop_stale=z, drop_dead=z, drop_out_of_time=z, enqueued=z,
            drop_overflow=z, drop_inf=z, vtime_us=z,
            fault_hist=jnp.zeros((NUM_FAULT_KINDS,), jnp.int32),
            kind_hist=jnp.zeros((num_kinds,), jnp.int32),
        )


BLOCK_FIELDS = MetricsBlock._fields


def metrics_from_observations(obs: Dict[str, np.ndarray]
                              ) -> Optional[Dict[str, np.ndarray]]:
    """Extract the per-seed metrics frame from an observation dict
    (the ``m_``-prefixed entries ``DeviceEngine.observe`` adds), or
    ``None`` when the sweep ran metrics-off."""
    per_seed = {k[len(OBS_PREFIX):]: np.asarray(v)
                for k, v in obs.items() if k.startswith(OBS_PREFIX)}
    return per_seed or None


def aggregate_metrics(per_seed: Dict[str, np.ndarray]) -> Dict[str, object]:
    """Fleet-aggregate frame: counters sum over the seed axis; histograms
    stay per-bin lists. JSON-serializable (``SweepResult.metrics``'s
    ``aggregate``)."""
    out: Dict[str, object] = {}
    for k, v in per_seed.items():
        s = np.asarray(v).sum(axis=0)
        out[k] = int(s) if np.ndim(s) == 0 else [int(x) for x in s]
    return out
