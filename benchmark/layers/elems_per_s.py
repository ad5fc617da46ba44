"""Elements shared per second over the whole window: the window's rounds x
participants x dim, over the seconds from the first round's start to the last
round's end. A mean, stalls between and inside rounds included, which is what
``round_s`` (a median) hides; a machine's stalls swing it, so it carries no
bound."""

name = "elems_per_s"
unit = "elements/s"
layer = "entry point"
moves = "round_s"
reads_spans = ("round",)


def reduce(spans, trace, cell):
    rounds = [s for s in spans if s.name == "round"]
    if not rounds:
        return None
    elapsed = max(s.end for s in rounds) - min(s.start for s in rounds)
    return len(rounds) * cell.elements_per_round / elapsed
