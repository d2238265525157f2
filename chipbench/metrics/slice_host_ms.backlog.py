"""Host milliseconds per stripe in cutting a fused write launch's sealed
rows into stripes and exact-length bodies and placing each body on its
device (the program's ``kernels.slice`` spans) over the traced run.
Nothing on a run without device operations (a CPU run), nor from a
program without the span."""

import program_spans


def read(run):
    return program_spans.ms_per_stripe(run, "kernels.slice")
