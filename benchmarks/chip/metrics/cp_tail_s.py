"""Mean duration of the program's ``pipeline.store`` spans that ran off
the saving thread, in s: the asynchronous tail alone (the deferred
digests, Pack, Place, Commit on the CP thread), without Plan and without
the wait in the queue.  Each carries the ``pipeline.plan`` span that
caused it as ``cause``."""

import span_reduce

SPAN = "pipeline.store"


def read(obs):
    spans = span_reduce.closed_spans(obs.get("spans") or [])
    savers = {(s["pid"], s["tid"]) for s in spans if s["name"] == span_reduce.SAVE}
    tails = [s["dur"] / 1e6 for s in spans
             if s["name"] == SPAN and (s["pid"], s["tid"]) not in savers]
    return sum(tails) / len(tails) if tails else None
