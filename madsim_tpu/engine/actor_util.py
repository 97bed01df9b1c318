"""Shared actor-side helpers: payload padding and the outbox layout.

EVERY actor family assembles the same (N peer messages + 1 timer)
Outbox shape through :func:`make_outbox` — the hand-written craft
reference (raft_actor) calls it directly, and the actor compiler
(madsim_tpu/actorc/compile.py) emits exactly one call per compiled
step for the spec-defined families (tpc, pb, paxos). Keeping the
layout in one place means a change to it cannot silently diverge the
actors — and the compiled/host-twin crosscheck (actorc/conformance.py)
now pins the layout bitwise per event on top. An actor that arms a
second timer per handler appends its row with :func:`add_timer`.
"""
from __future__ import annotations

import jax.numpy as jnp

from .core import EngineConfig, Outbox


def pad_payload(cfg: EngineConfig, words) -> jnp.ndarray:
    """(P,) payload row: the given words, zero-padded."""
    vals = [jnp.asarray(w, jnp.int32) for w in words]
    vals += [jnp.int32(0)] * (cfg.payload_words - len(vals))
    return jnp.stack(vals)


def bcast_payload(cfg: EngineConfig, n: int, words) -> jnp.ndarray:
    """(N, P) payload with the same words in every row."""
    return jnp.broadcast_to(pad_payload(cfg, words), (n, cfg.payload_words))


def make_outbox(cfg: EngineConfig, n: int, msg_valid, msg_kind, msg_payload,
                timer_valid, timer_kind, timer_dst, timer_delay,
                timer_payload) -> Outbox:
    """Assemble the (N peers + 1 timer) outbox layout."""
    app = lambda xs, x: jnp.concatenate(  # noqa: E731
        [jnp.asarray(xs), jnp.asarray(x)[None]], axis=0)
    return Outbox(
        valid=app(msg_valid, timer_valid),
        is_timer=app(jnp.zeros((n,), bool), jnp.asarray(True)),
        kind=app(msg_kind, timer_kind),
        dst=app(jnp.arange(n, dtype=jnp.int32),
                jnp.asarray(timer_dst, jnp.int32)),
        delay_us=app(jnp.zeros((n,), jnp.int32),
                     jnp.asarray(timer_delay, jnp.int32)),
        payload=jnp.concatenate([msg_payload, timer_payload[None]], axis=0),
    )


def add_timer(ob: Outbox, valid, kind, dst, delay, payload) -> Outbox:
    """``ob`` with one more timer row after its last: for an actor whose
    handler may arm a second timer (``outbox_cap`` one above the usual
    N + 1)."""
    app = lambda xs, x: jnp.concatenate(  # noqa: E731
        [xs, jnp.asarray(x, xs.dtype)[None]], axis=0)
    return Outbox(
        valid=app(ob.valid, valid),
        is_timer=app(ob.is_timer, True),
        kind=app(ob.kind, kind),
        dst=app(ob.dst, dst),
        delay_us=app(ob.delay_us, delay),
        payload=app(ob.payload, payload),
    )
