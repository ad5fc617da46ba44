"""Seeds a round that the feed's masked steps handed on: the program's
counter ``sda_fabric_fed_seeds_total`` (reached by dotted path) over the
rounds the process has run: the window's and the one of warm-up. In a round
that masks every row it is the cohort's rows; fewer, and the recipient cannot
unmask. Nothing where the program has no such counter (the parent's) or it
reads 0 (a round that masks nothing)."""

from benchmark import traffic

name = "feed.seeds_per_round"
unit = "seeds"
layer = "host feed"
moves = "round_s"
reads_spans = ()

COUNTERS = "sda_tpu.telemetry.snapshot"
SEEDS = "sda_fabric_fed_seeds_total"


def reduce(spans, trace, cell):
    counters = traffic.resolve(COUNTERS)(0).get("counters", ())
    seeds = sum(c["value"] for c in counters if c["name"] == SEEDS)
    if not seeds or not cell.rounds:
        return None
    return seeds / (cell.rounds + 1)
