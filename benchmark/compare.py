"""The comparison that decides ``correct``: the program's first steps
against the plain reference's.  Used alike by the chip run, the
control, the fault readings and the CPU tests.

Numbers compared (each with a limit of its own, from the cell's file):

* ``loss_step1`` .. ``loss_step3``: |program - reference| / |reference|.
* what the cell's task adds of its own (``tasks/<task>.py``
  ``extra_numbers``), e.g. one loss term that no discrete choice of
  the model enters.
* ``first_grad_worst_leaf``: the gap between the program's and the
  reference's norm of the first gradient as the optimizer gets it
  (the task's ``first_moment`` of the optimizer's state after one
  step: under SGD the momentum, gradient plus weight decay), by the
  worst leaf, over the larger of the reference's norm of that leaf and
  of the median leaf.
* ``delta3_worst_leaf``: the same for the parameters' change after
  the three steps.  Leaves whose reference gradient is under a
  thousandth of the median leaf's are left out (rule on the
  reference's gradient, not on names; in the detection task's SGD
  they are the frozen stem and stage, whose change is exactly zero on
  both sides).
* ``first_grad_median_leaf``, ``delta3_median_leaf``: the median
  leaf's gap of the same two: an update of the wrong size shows here
  first (both swing with the discrete choices; PERF.md section 4).
* ``frozen_moved``: the largest change or momentum of a leaf whose
  reference gradient is exactly zero or that the reference does not
  hold (frozen stem and stage, frozen batch-norm leaves); limit 0.
"""

from __future__ import annotations

import math
import statistics

TINY_GRAD_SHARE = 1e-3


def _leaf_gaps(prog, ref, leaves):
    """(worst gap, its leaf, median gap) of |prog - ref| over the
    larger of the reference's norm of the leaf and of the median leaf."""
    if not leaves:
        return math.inf, None, math.inf
    med = statistics.median(ref[k] for k in leaves)
    gaps = {}
    for k in leaves:
        if k not in prog or not math.isfinite(prog[k]):
            return math.inf, k, math.inf
        gaps[k] = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, statistics.median(gaps.values())


def numbers(program, reference, extra=None):
    """``program``/``reference``: dicts with ``loss`` (list),
    ``first_trace_norm`` and ``delta_norm`` ({leaf: norm}); the
    reference also has ``grad_norm``.  ``extra(program, reference)``:
    the task's own numbers.  Returns ({name: value},
    {name: worst leaf})."""
    out, where = {}, {}
    n = len(reference["loss"])
    for i in range(n):
        p = program["loss"][i] if i < len(program["loss"]) else math.nan
        r = reference["loss"][i]
        gap = abs(p - r) / max(abs(r), 1e-30)
        out[f"loss_step{i + 1}"] = gap if math.isfinite(gap) else math.inf
    if extra is not None:
        out.update(extra(program, reference))
    g = reference["grad_norm"]
    trained = [k for k in g if g[k] > 0.0]
    med = statistics.median(g[k] for k in trained) if trained else 0.0
    live = [k for k in trained if g[k] >= TINY_GRAD_SHARE * med]
    for name, key in (("first_grad", "first_trace_norm"),
                      ("delta3", "delta_norm")):
        worst, leaf, median = _leaf_gaps(program[key], reference[key], live)
        out[f"{name}_worst_leaf"], where[f"{name}_worst_leaf"] = worst, leaf
        out[f"{name}_median_leaf"] = median
    frozen = [k for k in program["delta_norm"] if g.get(k, 0.0) == 0.0]
    moved = [max(program["delta_norm"][k],
                 program["first_trace_norm"].get(k, 0.0)) for k in frozen]
    out["frozen_moved"] = max(moved) if moved else 0.0
    return out, where


def judge(values, limits):
    """(correct, [(name, value, limit)]) -- every number beside its
    limit; a number with no limit in the cell's file is not compared
    and not listed."""
    rows = [(k, values[k], limits[k]) for k in limits if k in values]
    missing = [k for k in limits if k not in values]
    ok = not missing and all(
        math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
