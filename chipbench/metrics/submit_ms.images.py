"""Engine: mean host time of one ``CutieEngine.submit`` (the program's
``repro.submit`` span, validation included) over the spans wholly in
the traced slice, in ms."""

from chipbench import program_spans


def read(run):
    return program_spans.mean_ms(run, "submit")
