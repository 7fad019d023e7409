"""DIFF Plan's packing loop per save, in ms: the program's ``diff.pack``
spans (on-device compaction of each dirty leaf's changed blocks and their
blocking copy to the host) summed over the window's ``chk.store`` spans.
Their ``leaves``, ``dirty_blocks``, ``bytes`` and ``n_pad`` arguments say
how much was packed and for which compiled dirty counts."""

import span_reduce

SPAN = "diff.pack"


def read(obs):
    s = span_reduce.per_save_s(obs.get("spans") or [], SPAN)
    return None if s is None else 1e3 * s
