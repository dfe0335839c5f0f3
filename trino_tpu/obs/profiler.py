"""Device-time truth: XLA cost-model operator attribution.

The problem this module solves (carried on ROADMAP since round 9):
per-operator instrumentation used to SPLIT fused kernel chains — wrapping
a node boundary forced the pending scan->filter->project chain to compose
at that node, so turning `collect_operator_stats` on changed which
executables ran (and pushed mesh programs off the fast path entirely).
The numbers lied exactly where a TPU engine needs them true.

The fix is the compiler's own cost model instead of fences between
dispatches: a fused chain records ONE measured device wall per dispatch
(`jax.block_until_ready` at CHAIN granularity — the same program the
un-instrumented query runs), and that wall is apportioned across the
chain's operators by per-step XLA cost analysis:

  - intermediate page avals come from `jax.eval_shape` walked through the
    chain steps (no execution, no compile);
  - each step's flops + bytes-accessed come from
    `jax.jit(step).lower(aval).cost_analysis()` — HLO-level cost
    analysis on the abstract program, no backend executable built;
  - weights are cached per (canonical chain key, input signature), the
    same keying discipline as the jit cache itself, so a warm chain
    never re-derives them.

Reference parity: the reference's OperationTimer charges wall to the
operator that ran between two nanoTime reads — affordable when operators
are separate Java calls. Here operators are regions of one XLA program,
so the cost model IS the boundary (PAPER.md §2.6: runtime-generated
kernels replace the bytecode whose per-operator accounting Trino gets
for free).

Fallbacks are deliberate: any cost-analysis failure degrades that step's
weight to 1.0 (equal split) rather than failing the query — attribution
is observability, never a correctness dependency.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

# (chain key, input signature) -> per-step weight tuple. Bounded FIFO:
# the population is the jit cache's key space, which the LRU there
# already bounds to the same order of magnitude.
_WEIGHTS: "collections.OrderedDict[Tuple, Tuple[float, ...]]" = \
    collections.OrderedDict()
_MAX_WEIGHT_ENTRIES = 1024
_LOCK = threading.Lock()


def tree_signature(args) -> Tuple:
    """Hashable structural signature of a pytree of arrays/scalars:
    treedef + per-leaf (dtype, shape, sharding, weak-typedness). Two
    argument sets with equal signatures lower to the same avals, so one
    compiled executable (and one weight vector) serves both."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = [treedef]
    for leaf in leaves:
        if hasattr(leaf, "dtype") and hasattr(leaf, "shape"):
            try:
                sharding = getattr(leaf, "sharding", None)
                hash(sharding)
            except TypeError:
                sharding = None
            sig.append((np.dtype(leaf.dtype).str, tuple(leaf.shape),
                        sharding, getattr(leaf, "weak_type", None)))
        else:
            # python scalar: jax gives it a weak-typed aval keyed by its
            # python type (bool before int: bool is an int subclass)
            sig.append(type(leaf))
    return tuple(sig)


def cost_dict(lowered) -> Dict[str, float]:
    """Flops / bytes-accessed estimate off a `jax.stages.Lowered` (dict
    or per-computation list depending on version/backend); {} when the
    backend can't say."""
    try:
        cost = lowered.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    return {"flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes": float(cost.get("bytes accessed", 0.0) or 0.0)}


def hlo_op_count(lowered) -> int:
    """Instruction count of the lowered module (StableHLO text lines
    with an SSA assignment) — the 'how big is this program' number
    compile accounting records per executable."""
    try:
        text = lowered.as_text()
    except Exception:
        return 0
    return sum(1 for line in text.splitlines() if " = " in line)


def _step_weight(fn, aval_in, group) -> Tuple[float, Any]:
    """(cost weight, output aval) for one chain step evaluated on
    abstract inputs. Weight = flops + bytes accessed: page kernels are
    memory-bound, so bytes dominate and flops break ties; the absolute
    scale cancels in the apportionment ratio."""
    try:
        out = jax.eval_shape(fn, aval_in, group)
    except Exception:
        return 1.0, aval_in
    try:
        cost = cost_dict(jax.jit(fn).lower(aval_in, group))
        w = cost.get("flops", 0.0) + cost.get("bytes", 0.0)
    except Exception:
        w = 0.0
    return max(w, 1.0), out


def _tail_weight(fn, aval_in) -> float:
    try:
        jax.eval_shape(fn, aval_in)
        cost = cost_dict(jax.jit(fn).lower(aval_in))
        return max(cost.get("flops", 0.0) + cost.get("bytes", 0.0), 1.0)
    except Exception:
        return 1.0


def chain_weights(key, build_chain, page, params) -> Tuple[float, ...]:
    """Per-step apportionment weights for a fused chain: one weight per
    step plus, when the chain fuses a blocking tail (partial
    aggregation), one trailing weight for the tail. `build_chain()` is
    `local_planner.chain_steps(...)`: the steps and the tail as the
    program runs them, deferred filters included. Cached per
    (canonical chain key, input signature); derivation walks avals
    through the chain with eval_shape and costs each step with the XLA
    cost model — no device work, no backend compile."""
    try:
        sig = (key, tree_signature((page,)))
        aval = jax.eval_shape(lambda p: p, page)
    except Exception:
        sig = None
    with _LOCK:
        got = _WEIGHTS.get(sig)
    if got is not None:
        return got
    steps, tail = build_chain()
    if sig is None:
        return (1.0,) * (len(steps) + (tail is not None))
    weights = []
    for fn, group in zip(steps, params):
        w, aval = _step_weight(fn, aval, tuple(group))
        weights.append(w)
    if tail is not None:
        weights.append(_tail_weight(tail, aval))
    out = tuple(weights)
    with _LOCK:
        while len(_WEIGHTS) >= _MAX_WEIGHT_ENTRIES:
            _WEIGHTS.popitem(last=False)
        _WEIGHTS[sig] = out
    return out


def apportion(wall_s: float, weights) -> Tuple[float, ...]:
    """Split a measured wall across steps proportionally to their cost
    weights (sums to wall_s up to float rounding)."""
    total = sum(weights)
    if total <= 0:
        n = max(len(weights), 1)
        return tuple(wall_s / n for _ in weights)
    return tuple(wall_s * w / total for w in weights)


def clear() -> None:  # for tests
    with _LOCK:
        _WEIGHTS.clear()
