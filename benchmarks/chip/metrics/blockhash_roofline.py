"""Share of the HBM roofline the digest pass reached, in %: the bytes of
the leaves it had to hash (each read once) over the chip's HBM bandwidth,
divided by the device time of the ``blockhash_pallas`` programs (padding,
both salted passes and the fold) in the traced window.  The pass is
bandwidth-bound, so bytes set its floor."""

PROGRAM = "blockhash_pallas"


def read(obs):
    seconds = obs.get("trace", {}).get("modules", {}).get(PROGRAM, 0.0)
    hashed = obs.get("hashed_bytes", 0)
    if seconds <= 0 or hashed <= 0:
        return None
    return 100.0 * hashed / obs["peaks"]["hbm_bytes_per_s"] / seconds
