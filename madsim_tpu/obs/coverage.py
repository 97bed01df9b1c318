"""Device-resident behavior-coverage ledger: the sweep's novelty signal.

A FoundationDB-style always-on hunt (PAPER.md) is only as good as its
ability to answer "are we still finding *new behaviors*?" while it runs.
This module turns the :class:`~madsim_tpu.obs.metrics.MetricsBlock`
histograms PR 5 already accumulates per world into exactly that signal,
with the DrJAX MapReduce-primitive shape (PAPERS.md): a *map* over
retiring worlds (hash each world's histograms into a behavior signature)
and an on-device *reduce* (psum/pmin of a fixed-size bucket sketch over
the mesh), so the hunt's coverage accounting costs **zero host pulls**
inside the sweep's superstep loop.

The signature is deliberately coarse — AFL-style: every histogram count
is first quantized to its power-of-two bucket (``bit_length``), then the
bucketed columns are FNV-1a-folded into one u32 per world. Two worlds
that delivered "about the same mix" of event kinds, drop causes, and
fault injections therefore share a signature; a world that took a new
qualitative path (a drop cause never seen, a fault survived differently,
an order-of-magnitude shift in an event kind) lands in a fresh bucket.
Exact counts would make every seed "novel" and the signal useless.

The ledger itself is ``K`` buckets carried as mesh-replicated device
arrays (``hits`` — worlds folded per bucket; ``first_seen`` — the lowest
seed id folded into the bucket). Folds happen at **retire time**: the
chunk/superstep bodies (engine/core.py ``_superstep_impl``,
parallel/sweep.py runners) detect the worlds whose ``active`` flag fell
during the chunk and scatter their signatures in, which gives each world
exactly one fold with no extra bookkeeping state — and makes the fold
sequence (and so the per-chunk ``novelty_curve``) identical between the
serial and pipelined orchestration loops, because both execute the same
chunk bodies in the same order (the bitwise contract of docs/perf.md
"Pipelined orchestration").

Order-invariance contract: ``hits`` (a count per bucket) and
``first_seen`` (a *minimum* seed id per bucket, not a temporal first)
do not depend on fold order, only on the folded set — which is what
lets a checkpoint→resume sweep reproduce them bit-identically (the
resume pre-pass folds the already-retired worlds it finds in the
checkpoint; tests/test_obs.py). Only ``novelty_curve`` is per-call (it
is the *history* of this run's chunks).

Like :class:`MetricsBlock` itself, everything here is read-only over the
simulation state: no RNG draw, queue lane, or actor input ever depends
on the ledger, so coverage-on sweeps walk bit-identical trajectories to
coverage-off (tier-1, tests/test_obs.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

# Default sketch width (buckets). 256 is far above the distinct-behavior
# counts observed on the in-repo actor families (tens), so hash
# collisions stay rare while the whole ledger is ~2 KB of device memory
# and one ~2 KB pull at sweep end.
DEFAULT_BUCKETS = 256

# FNV-1a 32-bit constants (the signature hash).
_FNV_SEED = 0x811C9DC5
_FNV_PRIME = 0x01000193

# Sentinel for "no seed folded into this bucket yet" inside device math
# (host-facing arrays use -1).
_NO_SEED = np.int32(2**31 - 1)


def _bit_length_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Per-element ``int.bit_length`` of a non-negative int array, as u32.

    The AFL-style count quantizer: 0→0, 1→1, 2..3→2, 4..7→3, ... Exact
    integer math (no float log), so signatures are bit-stable across
    backends.
    """
    x = x.astype(jnp.uint32)
    n = jnp.zeros(x.shape, jnp.uint32)
    for s in (16, 8, 4, 2, 1):  # static unroll: 5 shift/compare rounds
        hi = x >> s
        move = hi > 0
        n = n + jnp.where(move, jnp.uint32(s), jnp.uint32(0))
        x = jnp.where(move, hi, x)
    return n + (x > 0).astype(jnp.uint32)


def behavior_signature(mb) -> jnp.ndarray:
    """u32 behavior signature per world from a (batched) MetricsBlock.

    Hashes the per-event-kind histogram, the fault-injection histogram,
    and the drop-cause counters — each bucketed to its power of two —
    in a fixed column order with FNV-1a. Works on a single block or a
    batch (leading world axis); traceable under jit/vmap/shard_map.
    """
    cols = [mb.kind_hist[..., j] for j in range(mb.kind_hist.shape[-1])]
    cols += [mb.fault_hist[..., j] for j in range(mb.fault_hist.shape[-1])]
    cols += [mb.drop_loss, mb.drop_stale, mb.drop_dead,
             mb.drop_out_of_time, mb.drop_overflow, mb.drop_inf]
    h = jnp.full(jnp.shape(cols[0]), _FNV_SEED, jnp.uint32)
    for c in cols:
        h = (h ^ _bit_length_u32(c)) * jnp.uint32(_FNV_PRIME)
    return h


def ledger_zeros(n_buckets: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A fresh (hits, first_seen) ledger pair (mesh-replicated shapes)."""
    return (jnp.zeros((n_buckets,), jnp.int32),
            jnp.full((n_buckets,), -1, jnp.int32))


def fold_retired(hits, first_seen, mb, fold_mask, idx,
                 reduce_sum, reduce_min):
    """Fold the masked worlds' behavior signatures into the ledger.

    ``mb`` is the batched MetricsBlock, ``fold_mask`` a (W,) bool of
    worlds to fold (the caller computes "retired during this chunk, real
    seed id"), ``idx`` the (W,) slot→seed-id vector. ``reduce_sum`` /
    ``reduce_min`` reduce a replicated array over the mesh axes (psum /
    pmin inside a shard_mapped sweep; identity under plain use). Masked
    scatters go to a dump row, so the fold costs no branches.
    """
    k = hits.shape[0]
    sig = behavior_signature(mb)
    bucket = (sig % jnp.uint32(k)).astype(jnp.int32)
    slot = jnp.where(fold_mask, bucket, k)  # dump row for masked-out worlds
    add = jnp.zeros((k + 1,), jnp.int32).at[slot].add(1)[:k]
    add = reduce_sum(add)
    cand = jnp.full((k + 1,), _NO_SEED, jnp.int32).at[slot].min(
        idx.astype(jnp.int32))[:k]
    cand = reduce_min(cand)
    # first_seen is a MINIMUM seed id, not a temporal first: fold-order
    # invariant, so pipeline reordering and checkpoint/resume cannot
    # perturb it.
    best = jnp.minimum(jnp.where(first_seen >= 0, first_seen, _NO_SEED),
                       cand)
    first_seen = jnp.where(best < _NO_SEED, best, jnp.int32(-1))
    return hits + add, first_seen


def fold_retired_local(hits, first_seen, mb, fold_mask, idx):
    """:func:`fold_retired` for programs that see the FULL world axis.

    The in-loop variant the fused whole-hunt superstep uses
    (parallel/sweep.py): that program is a plain ``jit`` partitioned by
    GSPMD rather than a ``shard_map`` body, so its scatters already
    cover every world and the mesh reducers collapse to identity.
    Integer adds and minima are reduction-order invariant, so the
    resulting ledger is bitwise equal to the shard_mapped fold's.
    """
    ident = lambda x: x
    return fold_retired(hits, first_seen, mb, fold_mask, idx,
                        reduce_sum=ident, reduce_min=ident)


def distinct_count(hits: jnp.ndarray) -> jnp.ndarray:
    """Number of non-empty buckets — the ``distinct_behaviors`` scalar.
    (dtype-pinned sum: a bare jnp.sum widens to i64 under the x64 flag,
    which would break the i32 novelty-history carry — tracelint TRC003.)
    """
    return jnp.sum(hits > 0, dtype=jnp.int32)


@dataclasses.dataclass
class SweepCoverage:
    """Host-side coverage ledger of one sweep (``SweepResult.coverage``).

    ``novelty_curve[i]`` is the cumulative distinct-behavior count after
    the chunk ``SweepResult.n_active_chunks[i]`` (entrywise aligned with
    ``n_active_history`` — the same cadence, the same skew notes).
    Monotone non-decreasing by construction; deterministic across the
    pipelined/serial loops for the same seed set. ``distinct_behaviors``
    additionally includes the end-of-sweep fold of worlds still live at
    exit (a truncated world's partial histograms are a behavior too), so
    it is ``>= novelty_curve[-1]``.
    """

    n_buckets: int
    hits: np.ndarray             # (K,) worlds folded per bucket
    first_seen_seed: np.ndarray  # (K,) lowest seed id in bucket; -1 empty
    novelty_curve: np.ndarray    # cumulative distinct per executed chunk

    @property
    def distinct_behaviors(self) -> int:
        return int(np.count_nonzero(self.hits))

    @property
    def new_behaviors_per_chunk(self) -> np.ndarray:
        """The novelty curve's derivative: fresh buckets per chunk entry."""
        c = np.asarray(self.novelty_curve, np.int64)
        return np.diff(c, prepend=0)

    def to_json(self) -> Dict[str, object]:
        """Compact JSON-safe record (the ``observe=`` summary's block)."""
        curve = [int(x) for x in self.novelty_curve]
        return {
            "n_buckets": int(self.n_buckets),
            "distinct_behaviors": self.distinct_behaviors,
            "worlds_folded": int(self.hits.sum()),
            "novelty_first": curve[0] if curve else 0,
            "novelty_last": curve[-1] if curve else 0,
            "novelty_chunks": len(curve),
        }


def coverage_of_counters(counters: Dict[str, np.ndarray],
                         n_buckets: int = DEFAULT_BUCKETS
                         ) -> Dict[str, object]:
    """Host-side ledger over a dict of per-slot counter vectors.

    The bridge analog of the device fold: the kernel's ``BridgeMetrics``
    block is pulled once at sweep end (per *slot*, cumulative across
    recycled seeds — see bridge/kernel.py), and the same
    bucketize-then-FNV sketch runs in numpy over its columns. Column
    order is the sorted key order, so the sketch is stable across runs.
    """
    keys = sorted(counters)
    if not keys:
        return {"n_buckets": n_buckets, "distinct_behaviors": 0,
                "worlds_folded": 0}
    w = np.asarray(counters[keys[0]]).shape[0]
    h = np.full((w,), _FNV_SEED, np.uint32)
    for k in keys:
        col = np.asarray(counters[k], np.uint64)
        bl = np.zeros((w,), np.uint32)
        nz = col > 0
        # np bit_length via log2 on exact-integer u64 range would lose
        # precision; use the binary count loop like the device side.
        x = col.copy()
        for s in (32, 16, 8, 4, 2, 1):
            hi = x >> np.uint64(s)
            move = hi > 0
            bl[move] += np.uint32(s)
            x[move] = hi[move]
        bl += nz.astype(np.uint32)
        h = (h ^ bl) * np.uint32(_FNV_PRIME)
    buckets = h % np.uint32(n_buckets)
    hits = np.bincount(buckets, minlength=n_buckets)
    return {
        "n_buckets": int(n_buckets),
        "distinct_behaviors": int(np.count_nonzero(hits)),
        "worlds_folded": int(w),
    }


def coverage_from_device(n_buckets: int, hits, first_seen,
                         novelty: Optional[list]) -> SweepCoverage:
    """Assemble the host dataclass from the pulled ledger arrays."""
    return SweepCoverage(
        n_buckets=int(n_buckets),
        hits=np.asarray(hits, np.int64),
        first_seen_seed=np.asarray(first_seen, np.int64),
        novelty_curve=np.asarray(novelty or [], np.int64),
    )
