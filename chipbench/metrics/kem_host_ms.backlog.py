"""Host milliseconds per stripe in the RLWE KEM encapsulation of the seal
dispatch (the program's ``ingest.kem`` spans) over the traced run."""

import program_spans


def read(run):
    return program_spans.ms_per_stripe(run, "ingest.kem")
