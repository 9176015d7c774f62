"""Time per multi-get in the program's host-to-device copies (every
``race.to_device`` span: tables, query operands and, sharded, the
answers; each ends when what it shipped is on the device), mean over
the window's multi-gets, ms."""

from bench import spans


def read(run):
    return None if run.trace is None else spans.span_ms(
        run.trace, ["race.to_device"])
