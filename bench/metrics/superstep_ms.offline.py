"""Device milliseconds per superstep: the mean duration of the jitted
superstep (`make_superstep`'s `step_single`, module `jit_step_single`)
in the trace."""
from yardstick.readers import per_call_s

MODULE = "jit_step_single"


def read(rec):
    t, _ = per_call_s(rec, MODULE)
    return None if t is None else 1e3 * t
