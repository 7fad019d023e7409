"""Training thread's wait for room in the CP queue per save, in ms: the
program's ``cp.wait`` spans (one on every submit, zero long when the
previous tail had finished) summed over the window's ``chk.store``
spans."""

import span_reduce

SPAN = "cp.wait"


def read(obs):
    s = span_reduce.per_save_s(obs.get("spans") or [], SPAN)
    return None if s is None else 1e3 * s
