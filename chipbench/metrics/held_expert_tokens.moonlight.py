"""Model step: routed tokens per held expert per MoE layer per decode
step, the mean over the window's steps, from the executor's device-side
routing totals (``moe_assignments_total{site="decode", share="held"}``);
how near the deployment's per-expert load the cell runs."""

from chipbench import readings


def read(run):
    held = getattr(run.family, "held_expert_tokens", None)
    steps = readings.window_steps(run)
    return held(steps) if held is not None and steps else None
