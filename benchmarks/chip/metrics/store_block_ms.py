"""Mean host-clock time of a saving ``ctx.store`` call on the training
thread (directive + Plan, a wait for a full CP queue included), in ms."""


def read(obs):
    block = obs.get("store_block_s") or []
    return 1e3 * sum(block) / len(block) if block else None
