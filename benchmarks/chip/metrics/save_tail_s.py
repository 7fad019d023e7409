"""Mean ``StoreReport.seconds`` of the window's saves: Pack → Place →
Commit on the CP thread, its wait in the queue left out."""


def read(obs):
    tails = obs.get("save_tail_s") or []
    return sum(tails) / len(tails) if tails else None
