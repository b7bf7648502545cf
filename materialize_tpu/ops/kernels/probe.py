"""batched binary-search probe: fixed-depth searchsorted as one kernel.

The XLA lowering of `ops/search.py` is already branchless — ceil(log2(n)) + 1
unrolled gather/compare/select steps — but each step is a separate XLA gather
over the sorted array, so the array streams from HBM once per step. The
Pallas kernel runs the SAME unrolled loop with the sorted keys VMEM-resident
across all probe rows and all depth steps (the r2 probe-loop term: ~0.55 s of
a 2.05 s Q3 tick). Pure integer compare/select on identical operands in an
identical order, so outputs are bit-identical by construction.

`probe` is the single-key u32 search (join `_probe_ranges`, reduce
`lookup_accums`, output-slot owner searches); `probe2` is the two-key (hi,
lo) pair search backing `merge_consolidate` / `merge_consolidate_accums`.
Invariant per step: the insertion point lies in [pos, pos + cur]; all
positions i32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry


def _pred(a_elem: jnp.ndarray, q: jnp.ndarray, side: str) -> jnp.ndarray:
    return (a_elem < q) if side == "left" else (a_elem <= q)


def _pred2(a_hi, a_lo, q_hi, q_lo, side: str) -> jnp.ndarray:
    """(hi, lo) pair comparison: a < q (left) / a <= q (right) on the packed
    64-bit order, evaluated entirely in 32-bit lanes."""
    if side == "left":
        return (a_hi < q_hi) | ((a_hi == q_hi) & (a_lo < q_lo))
    return (a_hi < q_hi) | ((a_hi == q_hi) & (a_lo <= q_lo))


def _xla_searchsorted(a: jnp.ndarray, q: jnp.ndarray, side: str = "left"):
    """Reference oracle: the unrolled binary search over XLA gathers."""
    n = int(a.shape[0])
    pos = jnp.zeros(q.shape, dtype=jnp.int32)
    cur = n
    while cur > 1:
        half = cur >> 1
        mid = pos + (half - 1)  # compare a[pos + half - 1]
        pos = jnp.where(_pred(a[mid], q, side), pos + half, pos)
        cur -= half
    return pos + _pred(a[pos], q, side).astype(jnp.int32)


def _xla_searchsorted2(a_hi, a_lo, q_hi, q_lo, side: str = "left"):
    n = int(a_hi.shape[0])
    pos = jnp.zeros(q_hi.shape, dtype=jnp.int32)
    cur = n
    while cur > 1:
        half = cur >> 1
        mid = pos + (half - 1)
        go = _pred2(a_hi[mid], a_lo[mid], q_hi, q_lo, side)
        pos = jnp.where(go, pos + half, pos)
        cur -= half
    return pos + _pred2(a_hi[pos], a_lo[pos], q_hi, q_lo, side).astype(jnp.int32)


def _pallas_searchsorted(a: jnp.ndarray, q: jnp.ndarray, side: str = "left"):
    n = int(a.shape[0])
    if n == 0 or q.ndim != 1 or int(q.shape[0]) == 0:
        return _xla_searchsorted(a, q, side)
    m = int(q.shape[0])

    def kernel(a_ref, q_ref, out_ref):
        av = a_ref[...].reshape((n,))
        qv = q_ref[...]
        pos = jnp.zeros((1, m), dtype=jnp.int32)
        cur = n
        while cur > 1:
            half = cur >> 1
            mid = pos + (half - 1)
            elem = jnp.take(av, mid, mode="clip")
            pos = jnp.where(_pred(elem, qv, side), pos + half, pos)
            cur -= half
        last = jnp.take(av, pos, mode="clip")
        out_ref[...] = pos + _pred(last, qv, side).astype(jnp.int32)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        interpret=registry.pallas_interpret(),
    )(a.reshape(1, n), q.reshape(1, m))
    return out.reshape((m,))


def _pallas_searchsorted2(a_hi, a_lo, q_hi, q_lo, side: str = "left"):
    n = int(a_hi.shape[0])
    if n == 0 or q_hi.ndim != 1 or int(q_hi.shape[0]) == 0:
        return _xla_searchsorted2(a_hi, a_lo, q_hi, q_lo, side)
    m = int(q_hi.shape[0])

    def kernel(ah_ref, al_ref, qh_ref, ql_ref, out_ref):
        ah = ah_ref[...].reshape((n,))
        al = al_ref[...].reshape((n,))
        qh, ql = qh_ref[...], ql_ref[...]
        pos = jnp.zeros((1, m), dtype=jnp.int32)
        cur = n
        while cur > 1:
            half = cur >> 1
            mid = pos + (half - 1)
            go = _pred2(
                jnp.take(ah, mid, mode="clip"),
                jnp.take(al, mid, mode="clip"),
                qh,
                ql,
                side,
            )
            pos = jnp.where(go, pos + half, pos)
            cur -= half
        go = _pred2(
            jnp.take(ah, pos, mode="clip"),
            jnp.take(al, pos, mode="clip"),
            qh,
            ql,
            side,
        )
        out_ref[...] = pos + go.astype(jnp.int32)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        interpret=registry.pallas_interpret(),
    )(
        a_hi.reshape(1, n),
        a_lo.reshape(1, n),
        q_hi.reshape(1, m),
        q_lo.reshape(1, m),
    )
    return out.reshape((m,))


registry.register_kernel(
    "probe", xla=_xla_searchsorted, pallas=_pallas_searchsorted
)
registry.register_kernel(
    "probe2", xla=_xla_searchsorted2, pallas=_pallas_searchsorted2
)
