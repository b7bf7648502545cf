"""Join implementation planning: linear chain vs delta paths + stage keys.

The analogue of the reference's `JoinImplementation` transform
(src/transform/src/join_implementation.rs): given an N-way MirJoin with
equivalence classes over the flat column space, pick

- **linear** (binary chain arranging intermediates — differential
  `join_core`, linear_join.rs) for 2 inputs, or
- **delta** (one update path per input, no intermediate arrangements —
  delta_join.rs) for 3+ inputs,

and derive per-stage stream/lookup keys by walking the equivalence graph.
Each stage's next input is one connected to what is done, and among those
one whose lookup key covers a key the plan knows to be unique (`unique_keys`:
a Distinct's columns, a Reduce's group key) goes first, since it matches each
stream row at most once: a semijoin (`IN (SELECT ... GROUP BY ...)`) then
thins a delta path before its wide stages, as upstream's characteristics rank
delta-path inputs. Otherwise the lowest-numbered input goes first: a join with
no such input plans as it always has, a linear chain in input order. A linear
chain taken out of input order lists its order in `input_order`, and the
lowering projects its output back to the inputs' order. Equality members not
consumed as lookup keys are re-asserted as residual closure predicates
(correct even when classes span 3+ columns).
"""

from __future__ import annotations

from dataclasses import dataclass

from ..dataflow import plan as lir
from ..expr import relation as mir


@dataclass(frozen=True)
class JoinPlanned:
    """Physical join choice attached to MirJoin.implementation."""

    kind: str  # "linear" | "delta"
    lir_plan: object  # lir.LinearJoinPlan | lir.DeltaJoinPlan
    input_order: tuple  # for linear: order in which inputs are chained (stage i joins input_order[i + 1])
    residual_equalities: tuple  # ((global_col_a, global_col_b), ...)


def _offsets(arities):
    out, off = [], 0
    for a in arities:
        out.append(off)
        off += a
    return out


def unique_keys(e) -> tuple:
    """Sets of output columns of `e` the plan knows to be unique: a
    Distinct's columns, a Reduce's group key, seen through Filter, Map and
    Project. A source's declared key is not among them."""
    if isinstance(e, mir.MirDistinct):
        return (frozenset(range(mir.arity(e.input))),)
    if isinstance(e, mir.MirReduce):
        return (frozenset(range(len(e.group_key))),)
    if isinstance(e, (mir.MirFilter, mir.MirMap)):
        return unique_keys(e.input)
    if isinstance(e, mir.MirProject):
        first = {}
        for i, c in enumerate(e.outputs):
            first.setdefault(c, i)
        return tuple(
            frozenset(first[c] for c in key) for key in unique_keys(e.input) if key <= first.keys()
        )
    return ()


def plan_join_implementation(
    join: mir.MirJoin, enable_delta: bool = True, max_delta_inputs: int = 6
) -> JoinPlanned:
    arities = [mir.arity(i) for i in join.inputs]
    offsets = _offsets(arities)
    n = len(join.inputs)

    def owner(gcol: int) -> int:
        for k in range(n - 1, -1, -1):
            if gcol >= offsets[k]:
                return k
        return 0

    def local(gcol: int) -> int:
        return gcol - offsets[owner(gcol)]

    # equivalence classes as {input: [local cols]}
    classes = []
    for cls in join.equivalences:
        bymem: dict[int, list[int]] = {}
        for g in cls:
            bymem.setdefault(owner(g), []).append(local(g))
        classes.append((cls, bymem))

    def stage_keys(done: set[int], nxt: int, stream_cols: list):
        """Keys joining `nxt` to the accumulated inputs in `done`.

        stream_cols: list of (input, local) in current stream order.
        Returns (stream_key, lookup_key, used_class_idxs).
        """
        skey, lkey, used = [], [], []
        for ci, (_cls, bymem) in enumerate(classes):
            if nxt not in bymem:
                continue
            stream_side = None
            for inp in done:
                if inp in bymem:
                    stream_side = (inp, bymem[inp][0])
                    break
            if stream_side is None:
                continue
            skey.append(stream_cols.index(stream_side))
            lkey.append(bymem[nxt][0])
            used.append(ci)
        return tuple(skey), tuple(lkey), used

    keys = [unique_keys(i) for i in join.inputs]

    def connected(done: set[int]) -> list:
        return [
            k for k in range(n)
            if k not in done and any(k in bymem and any(d in bymem for d in done) for _c, bymem in classes)
        ]

    def keyed_input(done: set[int], stream_cols: list):
        """The lowest-numbered input connected to `done` whose lookup key
        covers one of its unique keys, else None."""
        for k in connected(done):
            lkey = set(stage_keys(done, k, stream_cols)[1])
            if any(key <= lkey for key in keys[k]):
                return k
        return None

    def next_input(done: set[int], stream_cols: list) -> int:
        # a keyed input connected to what's done, then any connected input,
        # then input order
        k = keyed_input(done, stream_cols)
        if k is not None:
            return k
        return (connected(done) or [k for k in range(n) if k not in done])[0]

    residuals = []
    for cls, bymem in classes:
        members = sorted(cls)
        for m in members[1:]:
            residuals.append((members[0], m))
    # residuals re-assert full class equality; the used lookup keys make most
    # of them tautological, which the closure MFP evaluates cheaply.

    if n == 2:
        done = {0}
        stream_cols = [(0, j) for j in range(arities[0])]
        skey, lkey, _ = stage_keys(done, 1, stream_cols)
        plan = lir.LinearJoinPlan(stages=(lir.JoinStage(skey, lkey),))
        return JoinPlanned("linear", plan, (0, 1), tuple(residuals))

    if n > max_delta_inputs or not enable_delta:
        # very wide joins (or delta joins disabled by dyncfg): chain linearly
        # in input order (delta paths grow O(n^2) lookups; reference caps
        # delta breadth similarly and has tested 64-relation linear chains,
        # README.md:46)
        stages = []
        done = {0}
        order = [0]
        stream_cols = [(0, j) for j in range(arities[0])]
        for _ in range(1, n):
            nxt = keyed_input(done, stream_cols)
            if nxt is None:
                nxt = min(k for k in range(n) if k not in done)
            skey, lkey, _ = stage_keys(done, nxt, stream_cols)
            stages.append(lir.JoinStage(skey, lkey))
            stream_cols += [(nxt, j) for j in range(arities[nxt])]
            done.add(nxt)
            order.append(nxt)
        plan = lir.LinearJoinPlan(stages=tuple(stages))
        return JoinPlanned("linear", plan, tuple(order), tuple(residuals))

    # delta join: one path per input
    paths, perms = [], []
    canonical = [(k, j) for k in range(n) for j in range(arities[k])]
    for k in range(n):
        done = {k}
        stream_cols = [(k, j) for j in range(arities[k])]
        path = []
        for _ in range(n - 1):
            nxt = next_input(done, stream_cols)
            skey, lkey, _ = stage_keys(done, nxt, stream_cols)
            path.append(
                lir.DeltaPathStage(other_input=nxt, stream_key=skey, lookup_key=lkey)
            )
            stream_cols += [(nxt, j) for j in range(arities[nxt])]
            done.add(nxt)
        paths.append(tuple(path))
        perms.append(tuple(stream_cols.index(c) for c in canonical))
    plan = lir.DeltaJoinPlan(paths=tuple(paths), permutations=tuple(perms))
    return JoinPlanned("delta", plan, tuple(range(n)), tuple(residuals))
