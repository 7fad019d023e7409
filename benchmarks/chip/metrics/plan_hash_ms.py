"""DIFF Plan's hashing loop per save, in ms: the program's ``diff.hash``
spans (on-device blockhash of each changed leaf and its blocking copy of
the digests to the host, then the dirty map) summed over the window's
``chk.store`` spans.  Their ``leaves``, ``skipped`` and ``bytes``
arguments say how much was hashed."""

import span_reduce

SPAN = "diff.hash"


def read(obs):
    s = span_reduce.per_save_s(obs.get("spans") or [], SPAN)
    return None if s is None else 1e3 * s
