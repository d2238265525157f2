"""Host milliseconds per stripe staging the fused write launches' inputs
and making the launch calls (the program's ``kernels.stage`` spans) over
the traced run."""

import program_spans


def read(run):
    return program_spans.ms_per_stripe(run, "kernels.stage")
