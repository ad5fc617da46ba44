"""Seconds a round of the fold in which the chip was not folding: the round's
``dispatch`` and ``fold`` spans (first put to ``block_until_ready``:
``engine.fold_s``) less the device seconds a round of the chunk step's own
program (the first of the round's ``steps``: the masked step, busiest chip).
What is left is the link's exposed share: the first chunk's crossing, and
whatever of the later crossings and of the host's pauses the fold did not
hide. A feed that overlaps link and fold leaves little; one that puts, waits
and then steps leaves the whole link. At least 0. In a traced run the link is
the profiler's (1.8 GB/s): worth reading once the harness evaluates layer
files untraced. Nothing without a device plane."""

import statistics

name = "feed.exposed_s"
unit = "s"
layer = "host feed"
moves = "round_s"
reads_spans = ("dispatch", "fold")


def reduce(spans, trace, cell):
    if trace is None or not cell.rounds or not cell.chunk_step_modules:
        return None
    busy = trace.max_busy_seconds(cell.chunk_step_modules)
    start = {s.round: s.start for s in spans if s.name == "dispatch"}
    end = {s.round: s.end for s in spans if s.name == "fold"}
    per_round = [end[r] - start[r] for r in start if r in end]
    if not busy or not per_round:
        return None
    return max(0.0, statistics.median(per_round) - busy / cell.rounds)
