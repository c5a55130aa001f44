"""Whole step: (prompt + output) tokens/s x 2 N flops per token over the
bf16 peak, in %.  N: the parameters a token multiplies on this chip,
the held routed experts at their expected share (``mla_moe_work.py``)."""

from chipbench import mla_moe_work, readings


def read(run):
    return (100.0 * readings.prompt_and_output_rate(run)
            * mla_moe_work.forward_flops_per_token(run.cell.config)
            / run.peaks["bf16_flops_per_s"])
