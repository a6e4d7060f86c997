"""The comparison that decides `correct`, and the work counts it yields.

Each answer the timed path produced (an exit order and a class per node)
is compared with the float64 reference on the same batch composition:
the reference is run once per engine batch over that batch's own support,
or once over the whole graph for a whole-graph job. Two numbers are
compared, each the widest over every answer (see `reference.gaps`):

* ``exit_gap``  — share of T_s by which a reference Eq. 8 distance lies on
  the wrong side of T_s for the answered exit order;
* ``logit_gap`` — by how much the reference logit of the answered class
  lies below the reference's best logit at that order.

``unanswered`` counts accepted requests that never got an answer (failed,
or still pending a minute after the window closed); its limit is 0.
The limits live in the configuration file under ``correct``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from .reference import Answers, Reference, gaps
from .work import nai_work


@dataclasses.dataclass
class Batch:
    """One batch as the program answered it, request by request."""
    nodes: np.ndarray
    orders: np.ndarray
    preds: np.ndarray


def _take(a: Answers, idx: np.ndarray) -> Answers:
    return Answers(orders=a.orders[idx], preds=a.preds[idx],
                   dist=a.dist[:, idx], logits=a.logits[:, idx])


def compare(ref: Reference, batches: List[Batch], *,
            control: Optional[Reference] = None, whole_graph: bool = False
            ) -> Dict:
    """Gaps of the program's answers (and, with `control`, of the control's
    answers on the same rows) against `ref`, plus the per-batch work."""
    g = ref.g
    exit_gap = logit_gap = 0.0
    ctl_exit = ctl_logit = 0.0
    compared = 0
    work = []
    everyone = np.ones(g.n, bool) if whole_graph else None
    cache = {}
    for b in batches:
        uniq, first = np.unique(b.nodes, return_index=True)
        key = uniq.tobytes() if whole_graph else None
        if key in cache:
            a, c, size = cache[key]
        else:
            mask = everyone if whole_graph else ref.support_mask(uniq)
            a = ref.answers(uniq, mask)
            c = control.answers(uniq, mask) if control is not None else None
            size = ref.support_size(mask)
            if whole_graph:
                cache[key] = (a, c, size)
        # a job answers every node once, in node order: its rows are `a`'s
        same = len(uniq) == len(b.nodes) and np.array_equal(uniq, b.nodes)
        e, lg = gaps(a if same else _take(a, np.searchsorted(uniq, b.nodes)),
                     ref.t_s, ref.t_min, ref.t_max, b.orders, b.preds)
        exit_gap, logit_gap = max(exit_gap, e), max(logit_gap, lg)
        if c is not None:
            e, lg = gaps(a, ref.t_s, ref.t_min, ref.t_max, c.orders, c.preds)
            ctl_exit, ctl_logit = max(ctl_exit, e), max(ctl_logit, lg)
        compared += len(b.nodes)
        rows, edges = size
        work.append(nai_work(rows=rows, edges=edges,
                             width=g.features.shape[1], steps=ref.t_max,
                             orders=b.orders[first], t_min=ref.t_min,
                             classes=g.num_classes))
    out = {"compared": compared, "exit_gap": exit_gap, "logit_gap": logit_gap,
           "work": work}
    if control is not None:
        out.update(control_exit_gap=ctl_exit, control_logit_gap=ctl_logit)
    return out


def verdict(found: Dict, limits: Dict, unanswered: int) -> List[Dict]:
    """The numbers compared, each beside its limit."""
    return [
        {"name": "exit_gap", "value": found["exit_gap"],
         "limit": limits["exit_gap"]},
        {"name": "logit_gap", "value": found["logit_gap"],
         "limit": limits["logit_gap"]},
        {"name": "unanswered", "value": unanswered, "limit": 0},
        {"name": "compared", "value": found["compared"], "limit": 1,
         "at_least": True},
    ]
