"""RLWE sessions per launch of the seal dispatch's KEM program, from the
program's counters ``kem.sessions`` / ``kem.launches`` over the traced
run (the window and its drain).  Nothing on a run without device
operations (a CPU run), nor from a program without these counters."""

import program_spans


def read(run):
    if not program_spans.on_chip(run):
        return None
    launches = run.telemetry.metrics.get("kem.launches")
    sessions = run.telemetry.metrics.get("kem.sessions")
    return sessions / launches if launches else None
