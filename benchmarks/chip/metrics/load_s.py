"""Mean duration of the program's ``train.load`` telemetry span (restore
through TCL: the recovery ladder, CHK5 read and checks, placement on the
device), in s."""

SPAN = "train.load"


def span_seconds(events, name):
    """Durations of the ``name`` spans in Chrome B/E events (E events carry
    no name: pair them per thread)."""
    stacks, out = {}, []
    for e in events:
        key = (e.get("pid"), e.get("tid"))
        if e.get("ph") == "B":
            stacks.setdefault(key, []).append(e)
        elif e.get("ph") == "E" and stacks.get(key):
            b = stacks[key].pop()
            if b.get("name") == name:
                out.append((e["ts"] - b["ts"]) / 1e6)
    return out


def read(obs):
    spans = span_seconds(obs.get("spans") or [], SPAN)
    return sum(spans) / len(spans) if spans else None
