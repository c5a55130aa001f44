"""Model step: share of the device-idle time inside the engine's
executor calls (``repro.batch``) that falls in the per-slot position
reads (``repro.cow``) and token reads (``repro.sync``) of the decode
step, in %."""

from chipbench import program_spans


def read(run):
    return program_spans.idle_share(run, ("cow", "sync"), "batch")
