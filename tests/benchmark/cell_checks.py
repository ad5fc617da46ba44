"""What the benchmark's tests hold one configuration and one cell to, as
functions of ``(root, name)``: the tests call them on this repo's manifest,
one case a cell, and on a temporary tree into which a cell with a round of its
own has been dropped as files (``bench_tree.add_toy_cell``), so that what
would refuse the next PR's cell is found in this one. They hold a cell to what
its round's module and the interface say (:mod:`benchmark.rounds`), not to the
constants of the one round the first cells have.
"""

from __future__ import annotations

import json
import pathlib
import re

from benchmark import harness, scopes, trace_reduce
from benchmark import rounds as rounds_interface
from benchmark import traffic as traffic_mod

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head|expansion|experts_per")
#: a v5e chip's memory as its runtime reports it (`bytes_limit`, chip run, PR 23)
HBM_BYTES = 16_909_336_064


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def manifest_of(root) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def stated_thresholds(scheme: dict) -> tuple:
    """``(privacy, reconstruction)`` as a configuration's ``scheme`` block
    implies them. A Shamir scheme hides its secrets behind ``privacy_threshold``
    random coefficients and reveals from that many shares plus one for each
    secret packed into a polynomial (one where ``secret_count`` is not stated);
    a scheme with no ``privacy_threshold`` is additive: every share but one
    says nothing, and all are needed."""
    if "privacy_threshold" not in scheme:
        return scheme["share_count"] - 1, scheme["share_count"]
    privacy = scheme["privacy_threshold"]
    return privacy, privacy + scheme.get("secret_count", 1)


def check_config(root, name: str) -> None:
    """One configuration's manifest entry and its file."""
    root = pathlib.Path(root)
    manifest = manifest_of(root)
    config = next(c for c in manifest["configs"] if c["name"] == name)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.fullmatch(config["name"])
    assert one_line(config["source"]) and one_line(config["why"])
    assert any(config["file"].startswith(p + "/") for p in manifest["paths"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.fullmatch(key) and not WIDTH.search(key), key
    stated = json.loads((root / config["file"]).read_text())
    assert stated["name"] == config["name"] and stated["source"] == config["source"]
    assert set(stated["reduced"]) == set(config["reduced"])
    for key in ("deployment", "layout", "scheme", "dim", "dropped_clerks", "assumed", "guarantees"):
        assert key in stated, key
    # the guarantees are the scheme's own, whatever the scheme
    guarantees = stated["guarantees"]
    privacy, reconstruction = stated_thresholds(stated["scheme"])
    assert guarantees["privacy_threshold"] == privacy, "not the scheme's privacy threshold"
    assert guarantees["reconstruction_threshold"] == reconstruction, (
        "not what the scheme needs to reconstruct"
    )
    assert "whole field [0, p)" in guarantees["share_randomness"]
    assert any(w["config"] == name for w in manifest["workloads"]), "a configuration no cell uses"


def check_cell(root, name: str) -> None:
    """One cell's manifest entry, its files found by name, and its round held
    to the interface and to what the metrics this cell reports read."""
    root = pathlib.Path(root)
    cell = next(w for w in manifest_of(root)["workloads"] if w["name"] == name)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.fullmatch(cell[key]), cell[key]
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    loaded = harness.load_cell(root, name)
    assert loaded.traffic.name == cell["traffic"] and loaded.chips == cell["chips"]
    assert loaded.config["name"] == cell["config"]
    # every cell reports setup_s, another end-to-end metric and a layer metric
    names = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and loaded.per_layer
    layers = harness.load_layers(root)
    for metric in loaded.per_layer:
        assert metric["name"] in layers
        assert metric["moves"] in names, "a layer metric where the metric it moves is not"

    # the round is the module the traffic file names; a file that names none
    # gets the default
    params = loaded.traffic.params
    module = harness.round_of(loaded)
    assert module.__name__ == loaded.traffic.round
    if "round" in params:
        assert params["round"] == module.__name__
    else:
        assert module.__name__ == traffic_mod.DEFAULT_ROUND
    # its spans, inside the harness's; among them those every round opens and
    # every span that a metric this cell reports reads
    opened = harness.span_names(loaded)
    assert opened == (harness.ROUND_SPAN, *module.span_names)
    assert len(set(opened)) == len(opened)
    for metric in loaded.per_layer:
        for span in layers[metric["name"]].reads_spans:
            assert span in opened, (
                f"cell {name!r} reports metric {metric['name']!r}, which reads the span "
                f"{span!r}; its round {module.__name__} opens {opened}"
            )
    for span in rounds_interface.CELL_WIDE_SPANS:
        assert span in opened, f"round {module.__name__} opens no span {span!r}"
    # what the round reads of the traffic file is there, and what names the
    # program or an adapter by dotted path resolves
    for key in getattr(module, "TRAFFIC_KEYS", ()):
        assert key in params, f"traffic {loaded.traffic.name!r} lacks {key!r}"
        if isinstance(params[key], str) and "." in params[key]:
            assert callable(traffic_mod.resolve(params[key])), key
    # packed_fold's own, for the cells of that round
    if module.__name__ == "benchmark.rounds.packed_fold":
        assert opened == ("round", "dispatch", "fold", "fetch", "epilogue", "check")
        assert params["accumulate"] in ("sum", "sum_mod_p")
        for key in module.TRAFFIC_KEYS:
            if key != "accumulate":
                assert callable(traffic_mod.resolve(params[key])), key
        assert loaded.traffic.chunk == loaded.config["chunk"]


# ---------------------------------------------------------------------------
# The compile rehearsal: a round's programs for a described chip
# ---------------------------------------------------------------------------


def _chunk(args):
    """The chunk step's chunk among its example arguments: the one that is
    sharded like the resident input, the largest."""
    return max(args, key=lambda a: a.size)


def _resident_bytes(cell, programs) -> int:
    """Bytes of the whole resident input on one chip: the chunk step is the
    first of the round's programs."""
    chunk = _chunk(programs[0][1])
    return cell.traffic.steps_per_pass * chunk.size * chunk.dtype.itemsize // cell.chips


def check_programs_compile_and_fit(root, name: str, devices) -> None:
    """Every program the cell's round runs in the window compiles for the
    described ``devices``, each under a name of its own; the chip holds the
    resident input beside the largest program's temporaries and output; and
    chips exchange something if, and only if, the cell has several."""
    cell = harness.load_cell(root, name)
    programs = harness.round_of(cell).steps(cell, list(devices))
    assert programs, "a round runs at least its chunk step on the device"
    modules, largest, collectives = [], 0, 0
    for jitted, args in programs:
        compiled = jitted.lower(*args).compile()
        memory = compiled.memory_analysis()
        largest = max(largest, memory.temp_size_in_bytes + memory.output_size_in_bytes)
        text = compiled.as_text()
        # the program's name as the join keys its operations by
        modules.append(next(iter(scopes.op_paths(text))).split("/", 1)[0])
        collectives += bool(trace_reduce.COLLECTIVE.search(text))
    # the trace tells programs apart by these names, and so do the join and
    # the chunk step's roofline
    assert len(set(modules)) == len(modules), modules
    total = _resident_bytes(cell, programs) + largest
    assert total < HBM_BYTES, (name, total, largest)
    if cell.chips > 1:
        assert collectives, "no program of a round over several chips exchanges anything"
    else:
        assert not collectives, "a one-chip round with a collective"


def check_input_compiles_and_fits(root, name: str, devices) -> None:
    """The program that makes one chunk of the resident input in set-up
    compiles for the described ``devices`` and fits beside the whole input."""
    cell = harness.load_cell(root, name)
    module = harness.round_of(cell)
    programs = module.steps(cell, list(devices))
    maker, maker_args = module.input_maker(cell, list(devices))
    memory = maker.lower(*maker_args).compile().memory_analysis()
    total = (
        _resident_bytes(cell, programs) + memory.argument_size_in_bytes
        + memory.temp_size_in_bytes + memory.output_size_in_bytes
    )
    assert total < HBM_BYTES, (name, total, memory)
