"""Device milliseconds per serving batch: the mean duration of the jitted
serving runner (`make_compiled_infer`'s `run`, module `jit_run`) in the
trace."""
from yardstick.readers import per_call_s

MODULE = "jit_run"


def read(rec):
    t, _ = per_call_s(rec, MODULE)
    return None if t is None else 1e3 * t
