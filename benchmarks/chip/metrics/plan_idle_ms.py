"""Device idle time per save while the training thread is inside the
program's ``pipeline.plan`` span, in ms: from the run's profiler trace
(``span_reduce.reduce_run``, matched to the run by the length of its
``window``), the first device's idle time in the window under
``pipeline.plan`` on the thread that holds the ``window`` span, over the
``chk.store`` spans there."""

import span_reduce


def read(obs):
    r = span_reduce.reduce_run((obs.get("trace") or {}).get("window_s"))
    return 1e3 * r["plan_idle_s"] / r["saves"] if r and r["saves"] else None
