"""Time per multi-get in copying the sharded kernel's padded answers
back to the host (``race.to_host``) while the chip runs nothing: the
span less the device-busy time inside it, which is the kernel it waits
for. Mean over the window's multi-gets, ms."""

from bench import spans


def read(run):
    return None if run.trace is None else spans.span_less_device_ms(
        run.trace, ["race.to_host"])
