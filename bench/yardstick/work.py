"""Operations and bytes that node-adaptive inference needs, counted from
the work itself: the support's real rows and edges, the real feature
width, the propagation steps and the exit orders. Padding, and anything a
particular backend adds (gathered copies, masked rows), is not counted,
so the counts are the same whatever backend runs the work and the least
time they imply is a true lower bound.

Per propagation step over a support of S rows and E directed edges (self
loops included) at width f: 2·E·f operations (a multiply and an add per
edge and feature) and, at the least, X read and written once and each
edge's source, destination and coefficient read once: 2·S·f·4 + 12·E
bytes. Per order l in [t_min, t_max) the Eq. 8 distance of each row still
active: 3·f operations, 2·f·4 bytes. Per batch row the linear head of its
exit order: 2·f·C operations, f·4 bytes in and 4 out, plus each head's
weights once.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

F32 = 4


def nai_work(*, rows: int, edges: int, width: int, steps: int,
             orders: np.ndarray, t_min: int, classes: int) -> Dict[str, float]:
    """Operations and bytes of one batch or one whole-graph job by part:
    ``prop`` (propagation), ``dist`` (exit distances), ``cls``
    (classification); `orders` are the exit orders of its batch rows."""
    orders = np.asarray(orders)
    nb = len(orders)
    active = sum(int((orders >= l).sum()) for l in range(t_min, steps))
    return {
        "steps": steps,
        "prop_flops": 2.0 * edges * width * steps,
        "prop_bytes": (2.0 * rows * width * F32 + 12.0 * edges) * steps,
        "dist_flops": 3.0 * active * width,
        "dist_bytes": 2.0 * active * width * F32,
        "cls_flops": 2.0 * nb * width * classes,
        "cls_bytes": nb * (width * F32 + F32) + steps * width * classes * F32,
    }


def total(work: Dict[str, float], parts=("prop", "dist", "cls")
          ) -> Tuple[float, float]:
    """(flops, bytes) of the named parts of one `nai_work` count."""
    return (sum(work[f"{p}_flops"] for p in parts),
            sum(work[f"{p}_bytes"] for p in parts))


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
