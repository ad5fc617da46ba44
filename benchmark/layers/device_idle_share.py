"""Share of the traced window in which no operation ran, on the chip that
idles most."""

name = "device.idle_share"
unit = "%"
layer = "device"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if trace is None:
        return None
    return 100.0 * trace.idle_share()
