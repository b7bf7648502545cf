"""segmented-sum-by-run: collapse equal-row runs of a sorted batch in one pass.

Backs `_consolidate_sorted` (ops/consolidate.py) and
`_consolidate_accums_sorted` (ops/reduce.py): given run-start flags computed
by full-row adjacent comparison over a canonically ordered batch, produce per
column ``out[i] = run_total if run_start[i] else 0`` — the value the XLA
chain ``segment_sum(col, cumsum(run_start)-1)[seg]`` masked by ``run_start``
computes with a cumsum, a scatter-add and a gather.

The Pallas kernel replaces that chain with a single pass over a VMEM-resident
tile: a backward *segmented* inclusive scan in ceil(log2(n)) shift-up steps
(the accelerator-native segmented-scan formulation, cf. arXiv:2505.15112;
the reduction-tree shape follows the atomic-free segmented reductions of
arXiv:2311.15810). Carrying end-of-run flags alongside the sums makes the
scan stop at segment boundaries:

    s[i]    <- col[i];   F[i] <- end_of_run[i]
    step d: s[i] <- s[i]           if F[i]
                    s[i] + s[i+d]  otherwise     (0 past the end)
            F[i] <- F[i] | F[i+d]

After the last step ``s[i]`` is the sum of ``col[i..end-of-run]``, so the run
total sits exactly at the run-start row. Integer addition is associative, so
the re-associated scan is BIT-identical to segment_sum — which is why this
kernel only accepts exact dtypes; float columns must take the XLA reference
(doc/KERNELS.md, bit-identity rule).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry


def _xla_run_sum(run_start: jnp.ndarray, cols: tuple) -> tuple:
    """Reference oracle: the segment_sum→gather chain, verbatim."""
    n = int(run_start.shape[0])
    seg = jnp.cumsum(run_start.astype(jnp.int32)) - 1
    return tuple(
        jnp.where(run_start, jax.ops.segment_sum(c, seg, num_segments=n)[seg], 0)
        for c in cols
    )


def _pallas_run_sum(run_start: jnp.ndarray, cols: tuple) -> tuple:
    cols = tuple(cols)
    n = int(run_start.shape[0])
    if not cols:
        return ()
    if n == 0 or any(
        jnp.issubdtype(c.dtype, jnp.floating) for c in cols
    ):
        # float sums would reassociate under the scan — keep the oracle
        return _xla_run_sum(run_start, cols)
    ncols = len(cols)
    rs = run_start.astype(jnp.int32).reshape(1, n)
    ins = [c.reshape(1, n) for c in cols]

    def kernel(rs_ref, *refs):
        in_refs, out_refs = refs[:ncols], refs[ncols:]
        start = rs_ref[...] != 0
        # end-of-run flags: the row BEFORE each run start ends a run, and the
        # last row always does
        end = jnp.concatenate(
            [start[:, 1:], jnp.ones((1, 1), dtype=jnp.bool_)], axis=1
        )
        for cref, oref in zip(in_refs, out_refs):
            s = cref[...]
            flag = end
            d = 1
            while d < n:
                s_up = jnp.concatenate(
                    [s[:, d:], jnp.zeros((1, d), dtype=s.dtype)], axis=1
                )
                f_up = jnp.concatenate(
                    [flag[:, d:], jnp.zeros((1, d), dtype=jnp.bool_)], axis=1
                )
                s = jnp.where(flag, s, s + s_up)
                flag = flag | f_up
                d <<= 1
            oref[...] = jnp.where(start, s, jnp.zeros_like(s))

    outs = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((1, n), c.dtype) for c in ins],
        interpret=registry.pallas_interpret(),
    )(rs, *ins)
    return tuple(o.reshape((n,)) for o in outs)


registry.register_kernel("run_sum", xla=_xla_run_sum, pallas=_pallas_run_sum)
