"""Host milliseconds per stripe blocked on the fused write launch's word
count fetch, the host waiting on the device (the program's
``kernels.fetch`` spans) over the traced run."""

import program_spans


def read(run):
    return program_spans.ms_per_stripe(run, "kernels.fetch")
