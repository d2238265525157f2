"""Host milliseconds per stripe in the engine's seal dispatch (staging,
KEM, launch), from the program's ``ingest.seal`` spans over the traced
run."""


def read(run):
    if run.telemetry is None:
        return None
    spans = [e for e in run.telemetry.tracer.events if e["name"] == "ingest.seal"]
    stripes = sum(int(e["attrs"].get("stripes", 0)) for e in spans)
    if not stripes:
        return None
    return sum(e["dur_ns"] for e in spans) / stripes / 1e6
