"""Model step: device-idle time inside each executor call of the engine
(the program's ``repro.batch`` span: pad, put, run, readback, head),
the mean over the spans wholly in the traced slice, in ms."""

from chipbench import program_spans


def read(run):
    return program_spans.mean_idle_ms(run, "batch")
