"""The fused Pallas step kernel (``EngineConfig(pallas=True)``).

Why: the lax step is a *sequence* of XLA fusions — queue pop (min +
gather), eligible mask, actor dispatch, outbox scatter — and on TPU each
fusion boundary is an HBM round trip for the world-state lanes it
touches. The per-step compute is tiny (thousands of int ops per world);
the cost is the state bytes crossing HBM several times per step, which
is exactly the ceiling the packed lane dtypes attack from the other
side (docs/perf.md "Roofline round 2"). Fusing the whole step into ONE
``pl.pallas_call`` keeps every lane — queue time/meta/payload, node
liveness, actor state — resident in VMEM for the duration of the step:
one load, one store, instead of one per fusion.

How: the kernel body *is* the engine's vmapped per-world step function.
Pallas kernels trace ordinary JAX ops over values loaded from refs, so
the same ``_build_step`` closure that defines the lax path defines the
kernel — which makes bitwise identity a construction property, not a
porting exercise, and it is gated anyway (tests/test_pallas_step.py,
the ``make smoke`` pallas-interpret leg) because a lowering bug would
break exactly this contract.

Deployment shape:

- **CPU / tier-1**: ``interpret=True`` (the auto default off-TPU) runs
  the kernel through the Pallas interpreter — same primitive sequence,
  bit-identical results, no Mosaic lowering required. This is what
  keeps the gate green in CI.
- **TPU**: not yet. Mosaic refuses the kernel (see
  ``MOSAIC_LOWERING_GAPS``), so a non-interpreted kernel raises
  ``NotImplementedError`` at engine construction instead of a Mosaic
  assertion at first trace. The intended shape: the whole batch in one
  kernel invocation, or gridded over the world axis via
  ``EngineConfig(pallas_block=B)`` — each grid step owns a
  ``(B, ...)`` block of every state leaf (worlds are independent, so
  the block split is semantics-free).
- ``input_output_aliases`` maps every state leaf onto its output slot,
  the in-kernel analog of the run loop's buffer donation: the state is
  updated in place, not double-buffered.

The kernel is a registered tracelint program (``engine.pallas_step``)
with its own budget-ledger entries, and is TRC005-checked like the lax
packed step.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


# What Mosaic (jax 0.9.0, libtpu 0.0.34) refuses when the kernel is
# compiled for a v5e, in the order the compiler hits them.
MOSAIC_LOWERING_GAPS = (
    "rank-0 hoisted constants: Mosaic 'supports only blocks of rank >= 1'",
    "the step's queue gathers: no Mosaic lowering in _gather_lowering_rule",
)


def _interpret_default() -> bool:
    """Interpret everywhere but on a real TPU backend: the interpreter
    is the portable (and CPU tier-1) execution mode; Mosaic lowering is
    the TPU one."""
    return jax.default_backend() != "tpu"


def make_pallas_step(step_one: Callable, cfg) -> Callable:
    """Build the batched step: ``WorldState[W] -> WorldState[W]`` as one
    ``pl.pallas_call``. ``step_one`` is the engine's per-world step
    closure (``DeviceEngine._build_step``); ``cfg`` supplies the
    ``pallas_block`` / ``pallas_interpret`` knobs."""
    from jax.experimental import pallas as pl

    interpret = cfg.pallas_interpret
    if interpret is None:
        interpret = _interpret_default()
    if not interpret:
        raise NotImplementedError(
            "EngineConfig(pallas=True) does not lower on TPU yet; Mosaic "
            "refuses the kernel for: " + "; ".join(MOSAIC_LOWERING_GAPS)
            + ". Use the lax step (pallas=False) on a TPU, or "
            "pallas_interpret=True.")
    batched_step = jax.vmap(step_one)

    def pallas_batched_step(state):
        leaves, treedef = jax.tree_util.tree_flatten(state)
        n = len(leaves)
        w = leaves[0].shape[0]

        def flat_step(*ls):
            s = jax.tree_util.tree_unflatten(treedef, ls)
            return jax.tree_util.tree_leaves(batched_step(s))

        block = cfg.pallas_block
        if block is not None and w % block:
            raise ValueError(
                f"pallas_block={block} does not divide the batch of {w} "
                f"worlds")
        gridded = block is not None and block < w
        bw = block if gridded else w

        # The step closure carries constant tables (the popcount
        # power-of-two vectors in lanes.prefix_count/queue.push_many,
        # arange masks, ...). Pallas kernels cannot capture constants —
        # and closure_convert only hoists *differentiable* ones, which
        # these integer tables are not — so the step is staged to a
        # jaxpr here (at the per-grid-step block width) and its consts
        # become explicit kernel inputs, re-bound from refs inside the
        # kernel body.
        closed = jax.make_jaxpr(flat_step)(
            *[jax.ShapeDtypeStruct((bw,) + l.shape[1:], l.dtype)
              for l in leaves])
        consts = [jnp.asarray(c) for c in closed.consts]
        nc = len(consts)

        def kernel(*refs):
            state_vals = [r[...] for r in refs[:n]]
            const_vals = [r[...] for r in refs[n:n + nc]]
            outs = jax.core.eval_jaxpr(closed.jaxpr, const_vals,
                                       *state_vals)
            for ref, val in zip(refs[n + nc:], outs):
                ref[...] = val

        kwargs = dict(
            out_shape=[jax.ShapeDtypeStruct(l.shape, l.dtype)
                       for l in leaves],
            # Every state leaf aliases its output slot: in-place update
            # inside the kernel, the donation story of the lax path.
            input_output_aliases={i: i for i in range(n)},
            interpret=True,
        )
        if gridded:
            # Grid over the world axis: grid step i owns worlds
            # [i*B, (i+1)*B) of every leaf. Worlds are independent, so
            # the blocked kernel is bitwise-identical to the monolithic
            # one; the index_map pins all trailing axes to block 0
            # (each block spans them whole). Hoisted constants have no
            # world axis: every grid step sees them whole.
            def spec(leaf):
                rest = leaf.shape[1:]
                return pl.BlockSpec(
                    (block,) + rest,
                    lambda i, _nr=len(rest): (i,) + (0,) * _nr)

            def const_spec(c):
                return pl.BlockSpec(
                    c.shape, lambda i, _nr=c.ndim: (0,) * _nr)

            kwargs.update(grid=(w // block,),
                          in_specs=[spec(l) for l in leaves]
                          + [const_spec(c) for c in consts],
                          out_specs=[spec(l) for l in leaves])
        out_leaves = pl.pallas_call(kernel, **kwargs)(*leaves, *consts)
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    return pallas_batched_step
