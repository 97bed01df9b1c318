"""Golden tracelint program fixtures.

Each function is a deliberately broken device program: the paired test
(tests/test_tracelint.py) traces it and asserts the matching TRC rule
fires — proving the rule would catch the same construct if it ever crept
into a real hot-path program. None of these run; they exist to be traced.
"""
import jax
import jax.numpy as jnp
import numpy as np


def leaky_callback(x):
    """TRC001 x2: a pure_callback and a debug.print (debug_print)."""
    y = jax.pure_callback(lambda v: np.asarray(v) + 1,
                          jax.ShapeDtypeStruct((), jnp.int32), x)
    jax.debug.print("x={x}", x=x)
    return y


def callback_in_scan(x):
    """TRC001 nested under a scan body — the walker must recurse."""
    def body(carry, _):
        jax.debug.print("c={c}", c=carry)
        return carry + 1, None
    out, _ = jax.lax.scan(body, x, None, length=3)
    return out


def unstable_sort(x):
    """TRC002: equal keys land in backend-chosen order."""
    return jax.lax.sort(x, is_stable=False)


def float_scatter_accum(x, idx, upd):
    """TRC002: float accumulation onto possibly-duplicate indices — the
    reduction order (and so the rounding) is backend-chosen."""
    return x.at[idx].add(upd)


def int_scatter_accum(x, idx, upd):
    """Clean twin of the above: integer adds are exact regardless of
    order, so no finding."""
    return x.at[idx].add(upd)


def x64_leaky_sum(mask):
    """TRC003 (output drift): an unpinned jnp.sum widens i32 -> i64 when
    jax_enable_x64 is set — the exact leak class tracelint's first
    self-scan found (and fixed) in the engine's occupancy reduction."""
    return jnp.sum(mask.astype(jnp.int32))


def f64_intermediate(x):
    """TRC003 (widened intermediate): the f64 cast silently truncates to
    f32 without the x64 flag, so the two settings round differently even
    though the output dtype is pinned."""
    return (x.astype(jnp.float64) * 2).astype(jnp.float32)


def clean_program(x):
    """No findings: dtype-pinned, stable, callback-free."""
    order = jnp.argsort(x, stable=True)
    return jnp.sum(jnp.take(x, order), dtype=jnp.int32)
