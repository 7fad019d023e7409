"""Share of the chip's bf16 peak that the window's steps needed, in %:
operations a step requires (the reference family's ``step_flops``: no
recomputation, no capacity padding, causal attention) times steps over
the window's host clock, over the peak.  Bounds every kernel roofline of
the step."""


def read(obs):
    steps, window = obs.get("steps", 0), obs.get("window_s", 0.0)
    if not steps or window <= 0:
        return None
    return 100.0 * obs["flops_per_step"] * steps / window / obs["peaks"]["bf16_flops"]
