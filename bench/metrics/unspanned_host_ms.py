"""Host time per multi-get that the program's spans do not name: the
benchmark's ``multiget`` span less the device-busy time and less the
union of all ``race.*`` spans inside it (the adapter's own copy of the
answers, Python between spans). Mean over the window's multi-gets, ms;
None where the program opens no such span."""

from bench import spans


def read(run):
    return None if run.trace is None else spans.unspanned_ms(run.trace)
