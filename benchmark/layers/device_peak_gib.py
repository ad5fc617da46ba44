"""Peak device memory after the window, the largest over the cell's chips.
Guards a fit; moves nothing."""

name = "device.peak_gib"
unit = "GiB"
layer = "device"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if not cell.memory_peak_bytes:
        return None
    return cell.memory_peak_bytes / 2**30
