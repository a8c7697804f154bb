"""Controls: the program with one guarantee of the deployment broken.

Every configuration states exact answers.  A control breaks that the way a
later change might be tempted to, and a run under it must come out not
correct:

* ``phase2_rounds`` — phase 2 stops after ``rounds`` bidirectional rounds
  (an early exit: a path longer than ``2 · rounds`` edges is missed, so a
  true answer comes back false).  The control of the true mixes, whose
  answers phase 2 decides.
* ``filters_only`` — the program's own ``answer_plan(filters_only=True)``
  path: a query the filter cascade cannot refute is answered true without
  the exact search.  The control of the false mixes.

A mix names its control in its file: ``{"name": ..., **parameters}``.
"""
from __future__ import annotations

import functools


def apply(control: dict) -> None:
    """Patch the program in this process; call before the server starts.
    Compiled programs are dropped, so none traced before the patch runs."""
    import jax

    from repro.core import tdr_query

    jax.clear_caches()

    name = control["name"]
    if name == "phase2_rounds":
        rounds = int(control["rounds"])
        loop = tdr_query._bidi_loop

        def capped(f0, b0, push_f, push_b, cor_w, sup_need, max_rounds):
            return loop(f0, b0, push_f, push_b, cor_w, sup_need,
                        min(max_rounds, rounds))

        tdr_query._bidi_loop = capped
    elif name == "filters_only":
        tdr_query.answer_plan = functools.partial(tdr_query.answer_plan,
                                                  filters_only=True)
    else:
        raise ValueError(f"unknown control {name!r}")
