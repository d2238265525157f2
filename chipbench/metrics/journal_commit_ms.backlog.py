"""Host milliseconds per stripe in the journal commit of the catalog
records, fsyncs and record building included (the program's
``ingest.journal`` spans) over the traced run."""

import program_spans


def read(run):
    return program_spans.ms_per_stripe(run, "ingest.journal")
