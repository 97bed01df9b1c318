"""Indexing primitives for the device engine: one-hot writes, tiny gathers.

The doctrine, refined by measurement over two perf rounds
(docs/perf.md):

- **Single-slot writes** (``upd``/``upd2``) stay one-hot mask + select:
  a lone ``x.at[i].set(v)`` with a traced index lowers to a scatter XLA
  cannot fuse, while the mask write fuses into the surrounding kernel
  and vectorizes over the world axis for free.
- **Reads** (``take_small``) from a tiny source axis (nodes N ≤ 8, log
  rows L ≤ 64, outbox M ≤ 8) depend on the backend. On the CPU a real
  gather: the one-hot select costs k·m·width ops per read — measured as
  one of the step's dominant flop consumers there — while the gather is
  priced at ~zero. On a TPU the opposite: XLA's TPU cost model prices
  each vmapped gather at ~0.5 MB per world (a step held ~50 of them,
  ~5.5 MB/world/step, and the 524,288-world headline did not finish a
  sweep in 30 minutes on the chip), against ~160 B for the select
  chain. Both forms clamp out-of-range indices, so values are equal.
  Re-measured on today's code (CPU, 4 cores, PR 21): the select forms
  make the 8,192-seed headline sweep 4-6x slower warm (8.0-10.7 s vs
  1.6-2.1 s) and the 2,048-world chaos sweep 13-20x (23.8-32.1 s vs
  1.5-1.9 s), so the CPU keeps the gathers.
- **The queue insert** (``queue.push_many``) is the one deliberate
  scatter on the CPU: M rows, computed slots, in-place under buffer
  donation — see its docstring for why it beats both the unrolled
  one-hot chain and a (Q,)-gather-driven rewrite. On a TPU it writes the
  same rows with one select per row.

The form follows the platform the program is traced for
(:func:`gathers_are_cheap`): the default device's when one is set —
``jax.default_device(cpu)`` on a TPU host traces the CPU's program, as
the chip-vs-CPU crosscheck and a CPU-placed bridge kernel do — else the
default backend's.

Anything not covered above goes through these helpers rather than raw
``x[i]`` / ``.at[i]`` so the layout decisions keep exactly one home.

Round 2 (docs/perf.md "Roofline round 2") adds the **lane dtype
registry**: most engine lanes carry values that fit 8 or 16 bits —
node ids, role/decision codes, queue slot indices and depths, log
positions, payload words — but historically rode int32, so the step's
HBM traffic (and the worlds-per-chip ceiling) was ~2x what the data
needs. :class:`Lanes` names one dtype per lane *category*; the packed
profile (``EngineConfig(packed=True)``, the default) narrows them,
while virtual time, RNG cursors and unbounded counters stay wide.
Discipline, enforced by tracelint TRC005 on the registered packed
programs:

- **wide in flight, narrow at rest**: queue/outbox events and all
  handler arithmetic stay int32; storage lanes narrow. Every narrow
  *read* is widened HERE (:func:`widen` — the one sanctioned
  narrow-to-wide conversion site), every narrow *write* goes through a
  saturating :func:`narrow` (or the wrapping :func:`narrow_wrap` for
  the mod-256 generation lane), so overflow behavior is explicit at
  every boundary rather than an accident of two's-complement wrap.
- the reference int32 profile stays alive behind
  ``EngineConfig(packed=False)`` for bitwise crosscheck, exactly like
  ``sequential_insert`` does for the fused queue insert.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Lanes(NamedTuple):
    """Dtype registry for the engine's state lanes, by category.

    - ``node``: node ids (``src``/``dst``/``voted_for``; -1 sentinels
      included). Packed: int8 — EngineConfig rejects ``n_nodes > 127``.
    - ``code``: small enumerations — event kinds, fault ops, drop-cause
      codes, role/decision codes, the mod-256 generation lane. Packed:
      int8 (event kinds are already capped at 64 by DeviceEngine).
    - ``slot``: queue slot indices and depths, log indices, terms,
      views, epochs — anything bounded by a capacity knob. Packed:
      int16 — EngineConfig rejects ``queue_cap > 32767``.
    - ``payload``: queue payload words *at rest*. Packed: int16; wide
      values the engine itself stores (net-config fault params) are
      split across two words (:func:`split_wide`/:func:`join_wide`),
      actor payloads saturate at the push boundary.
    - ``time`` / ``counter``: virtual-time microseconds and unbounded
      counters — ALWAYS int32 (as are RNG lanes, uint32). Listed so the
      registry names every category, not just the narrowed ones.

    Bitmask lanes (vote/ack sets, ``won_terms`` words) stay int32 in
    both profiles: their width is the bit capacity, not a value range.
    """

    node: Any
    code: Any
    slot: Any
    payload: Any
    time: Any = jnp.int32
    counter: Any = jnp.int32


#: Reference profile: every lane rides int32 (the pre-round-2 layout).
WIDE = Lanes(node=jnp.int32, code=jnp.int32, slot=jnp.int32,
             payload=jnp.int32)

#: Packed profile: ~0.6x the state bytes of :data:`WIDE` on the
#: canonical raft config (the ledgered ``state_bytes_per_world``).
PACKED = Lanes(node=jnp.int8, code=jnp.int8, slot=jnp.int16,
               payload=jnp.int16)


def widen(x) -> jnp.ndarray:
    """Narrow-lane read: widen to int32.

    THE sanctioned narrow-to-wide conversion site (tracelint TRC005
    flags any i8/i16-to-i32 convert in a registered packed program that
    does not originate here): all handler arithmetic runs int32, so
    every narrow state read passes through this exactly once. Pinned to
    int32 explicitly — never a weak Python int — so the x64 flag cannot
    widen it further (TRC003).
    """
    return jnp.asarray(x).astype(jnp.int32)


def narrow(x, dtype) -> jnp.ndarray:
    """Narrow-lane write: saturate into ``dtype``.

    The explicit guard at every narrow write boundary: values are
    clipped to the target's representable range before the cast, so an
    out-of-range value (a term past 32767, an oversized actor payload
    word) pins at the rail instead of wrapping silently. When ``dtype``
    is not strictly narrower (the WIDE profile), this is a plain cast —
    the reference path pays zero extra ops.
    """
    x = jnp.asarray(x)
    dt = jnp.dtype(dtype)
    if x.dtype == dt:
        return x
    if (jnp.issubdtype(x.dtype, jnp.integer) and jnp.issubdtype(dt, jnp.integer)
            and jnp.iinfo(dt).bits < jnp.iinfo(x.dtype).bits):
        info = jnp.iinfo(dt)
        x = jnp.clip(x, info.min, info.max)
    return x.astype(dt)


def narrow_wrap(x, dtype) -> jnp.ndarray:
    """Narrow-lane write with WRAP semantics — for lanes whose contract
    is modular arithmetic (the generation lane compares mod 256,
    ``queue.GEN_MASK``): a two's-complement truncating cast, explicit at
    the call site so wrap-vs-saturate is a stated decision, never a
    default."""
    return jnp.asarray(x).astype(dtype)


def split_wide(v):
    """Split an int32 value into two int16-range words ``(lo, hi)``.

    The engine's own wide payloads (net-config fault params: latency µs
    up to 2^31, loss ppm up to 1e6) ride the packed payload lane as two
    words. The low half is sign-folded into [-32768, 32767] so it
    passes the saturating :func:`narrow` untouched; :func:`join_wide`
    reassembles exactly.
    """
    v = jnp.asarray(v, jnp.int32)
    lo = ((v & 0xFFFF) ^ 0x8000) - 0x8000
    hi = v >> 16
    return lo, hi


def join_wide(lo, hi) -> jnp.ndarray:
    """Inverse of :func:`split_wide` (operands already widened int32)."""
    return (jnp.asarray(lo, jnp.int32) & 0xFFFF) \
        | (jnp.asarray(hi, jnp.int32) << 16)


def onehot(i, n: int) -> jnp.ndarray:
    """(n,) bool mask selecting index ``i``.

    Out-of-range ``i`` selects *nothing* (drop semantics: sel yields 0/False,
    upd is a no-op) — unlike jit-mode ``x[i]``, which clamps to the edge.
    Callers with possibly-wild indices must clip first.
    """
    return jnp.arange(n) == jnp.asarray(i, jnp.int32)


def _shaped(mask: jnp.ndarray, ndim: int) -> jnp.ndarray:
    """Reshape a (n,) mask to broadcast over trailing dims of an ndim array."""
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def sel(x: jnp.ndarray, i) -> jnp.ndarray:
    """``x[i]`` over axis 0 without a gather. x: (n, ...) → (...)."""
    m = _shaped(onehot(i, x.shape[0]), x.ndim)
    if x.dtype == jnp.bool_:
        return jnp.any(x & m, axis=0)
    return jnp.sum(jnp.where(m, x, 0), axis=0).astype(x.dtype)


def sel2(x: jnp.ndarray, i, j) -> jnp.ndarray:
    """``x[i, j]`` over the two leading axes. x: (n, m, ...) → (...)."""
    return sel(sel(x, i), j)


def sel_many(x: jnp.ndarray, idxs: jnp.ndarray) -> jnp.ndarray:
    """``x[idxs]`` for a 1-D ``x`` and a vector of indices, gather-free.

    x: (n,), idxs: (k,) → (k,). The (k, n) one-hot matrix contracts over n;
    for the engine's tiny n this fuses into the surrounding elementwise work.
    """
    m = jnp.arange(x.shape[0])[None, :] == idxs[:, None]
    return jnp.sum(jnp.where(m, x[None, :], 0), axis=1).astype(x.dtype)


def prefix_count(mask: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix count: how many True lanes strictly precede each
    lane.

    For the engine's queue widths (n ≤ 64) the mask packs into one or
    two uint32 words (a ``where`` against the constant powers-of-two
    vector + a sum); each lane then ANDs the word with a *constant*
    below-me bitmask and ``population_count``s it. Two subtleties make
    this the cheapest form in practice, not just on paper:

    - XLA CPU *clones* elementwise producer chains into every consumer
      fusion, so the chain is pinned behind an identity gather (a
      materialization point fusion cannot clone through) — without it,
      the queue's three lane writes would each re-price the whole
      prefix (docs/perf.md r7).
    - The alternative, ``jnp.cumsum``, prices flat but its hierarchical
      scan lowering allocates ~1 KB/world of scratch inside the step —
      the difference between fitting 1.2× state in peak memory and not.

    Larger n falls back to ``jnp.cumsum`` (the word trick scales as
    n·(n/32) and stops winning past two words).
    """
    n = mask.shape[0]
    if n > 64:
        inc = jnp.cumsum(mask.astype(jnp.int32))
        return jnp.concatenate([jnp.zeros((1,), jnp.int32), inc[:-1]])
    counts = jnp.zeros((n,), jnp.int32)
    for w in range((n + 31) // 32):
        lanes = min(32, n - 32 * w)
        pow2 = jnp.asarray(np.uint32(1) << np.arange(lanes, dtype=np.uint32),
                           jnp.uint32)
        word = jnp.sum(jnp.where(mask[32 * w:32 * w + lanes], pow2,
                                 jnp.uint32(0)))
        # below[s]: bits of word w strictly below lane s (zero before the
        # word, all-ones once past it) — a host-built constant vector.
        rel = np.clip(np.arange(n) - 32 * w, 0, 32)
        partial = (np.uint32(1) << np.minimum(rel, 31).astype(np.uint32)) \
            - np.uint32(1)
        below = jnp.asarray(np.where(rel < 32, partial,
                                     np.uint32(0xFFFFFFFF)), jnp.uint32)
        counts = counts + lax.population_count(word & below) \
            .astype(jnp.int32)
    if not gathers_are_cheap():
        return counts
    # Identity gather = an explicit materialization point (see docstring).
    return jnp.take(counts, jnp.arange(n), axis=0)


def gathers_are_cheap() -> bool:
    """Whether the programs being traced may use real gathers: True on
    every platform but the TPU (see the module docstring). Read at trace
    time from the default device when one is set (``jax.default_device``
    is part of jit's cache key, so each placement traces its own form),
    else from the default backend; a test that compiles for a described
    TPU without one steers it."""
    dev = jax.config.jax_default_device
    platform = dev if isinstance(dev, str) else getattr(dev, "platform",
                                                        None)
    return (platform or jax.default_backend()) != "tpu"


def deinterleave(x: jnp.ndarray):
    """``(x[0::2], x[1::2])`` of a 1-D ``x`` of even length. Under vmap
    the strided index is a gather; on the TPU a strided static slice
    instead (the gather made phase A's step 24,781 bytes per world in
    the v5e cost model vs 15,312). On the CPU the gather stays: the slice
    raised the engine programs' cost-model flops 35-54% and the 2,048-
    world chaos sweep 8-33% warm (my CPU runs, PR 21)."""
    if gathers_are_cheap():
        return x[0::2], x[1::2]
    n = x.shape[0]
    return lax.slice(x, (0,), (n,), (2,)), lax.slice(x, (1,), (n,), (2,))


def take_small(x: jnp.ndarray, idxs: jnp.ndarray) -> jnp.ndarray:
    """``x[idxs]`` as a REAL gather — for tiny leading axes only.

    x: (m, ...), idxs: (k,) → (k, ...). The one place the engine prefers a
    gather over a one-hot contraction: when the *source* axis is tiny
    (m ≲ 8, e.g. an outbox-sized table) but the index vector is long
    (k = queue capacity) and the rows are wide (payload words), the
    one-hot select costs k·m·width ops — the very per-slot rewrite cost
    :func:`~madsim_tpu.engine.queue.push_many` exists to eliminate —
    while the gather reads each destination row once.

    Out-of-range indices clamp to the edge ("clip" mode — measured
    cheaper post-fusion than both ``promise_in_bounds``'s at-get lowering
    and "wrap"); callers with possibly-wild indices get edge values and
    must mask the result. Where gathers are not cheap (the TPU) the same
    values come from a select chain over the m source rows.
    """
    idxs = jnp.asarray(idxs, jnp.int32)
    if gathers_are_cheap():
        return jnp.take(x, idxs, axis=0, mode="clip")
    idxs = jnp.clip(idxs, 0, x.shape[0] - 1)
    pick = idxs.reshape(idxs.shape + (1,) * (x.ndim - 1))
    out = jnp.broadcast_to(x[0], idxs.shape + x.shape[1:])
    for i in range(1, x.shape[0]):
        out = jnp.where(pick == i, x[i], out)
    return out


def upd(x: jnp.ndarray, i, v) -> jnp.ndarray:
    """``x.at[i].set(v)`` over axis 0 without a scatter.

    The written value passes through the saturating :func:`narrow` when
    ``x`` carries a packed lane dtype — every one-hot write is thereby a
    guarded narrow-write boundary for free (wrap-semantics lanes
    pre-wrap via :func:`narrow_wrap` before calling)."""
    m = _shaped(onehot(i, x.shape[0]), x.ndim)
    return jnp.where(m, narrow(v, x.dtype), x)


def upd2(x: jnp.ndarray, i, j, v) -> jnp.ndarray:
    """``x.at[i, j].set(v)`` over the two leading axes (saturating like
    :func:`upd`)."""
    m = (_shaped(onehot(i, x.shape[0]), x.ndim)
         & _shaped(onehot(j, x.shape[1]), x.ndim - 1)[None])
    return jnp.where(m, narrow(v, x.dtype), x)
