"""Model step: least time the chip needs for each traced decode step of
the MLA + MoE model (every held weight read once, plus the live latent
rows as the executor's ``pos`` holds them and the new rows written, at
HBM bandwidth; or its flops at the bf16 peak, whichever is longer;
``mla_moe_work.py``) over the device time of the decode program, in %."""

from chipbench import readings


def read(run):
    drv = run.family
    return readings.roofline_share(
        run, lambda step: drv.decode_needed_s(step.contexts, run.peaks),
        drv.is_decode_module)
