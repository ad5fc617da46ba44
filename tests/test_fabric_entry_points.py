"""An entry point a benchmark cell binds has no option the cell does not set.

A traffic file (``benchmark/traffic/*.json``, read here and never edited)
names the program's entries by dotted path: the chunk ``engine``, and either
the host ``epilogue`` and the ``reconstruct`` or the round ``driver`` that
pairs them itself (PR 34). Each must resolve, and none of its parameters may
carry a default: a default is a second behaviour that no cell measures (the
``draw=`` and ``exact=`` that a second benchmark used to select).
"""

import importlib
import inspect
import json
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
TRAFFIC = sorted((REPO / "benchmark" / "traffic").glob("*.json"))
BOUND = ("engine", "epilogue", "reconstruct")
#: what a traffic file that names the program's round driver binds instead
BOUND_BY_DRIVER = ("engine", "driver")


def resolve(dotted: str):
    module, name = dotted.rsplit(".", 1)
    return getattr(importlib.import_module(module), name)


def test_there_are_traffic_files_to_read():
    assert len(TRAFFIC) >= 4


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda path: path.stem)
def test_every_bound_entry_resolves_and_has_no_defaulted_parameter(path):
    traffic = json.loads(path.read_text())
    for role in BOUND_BY_DRIVER if "driver" in traffic else BOUND:
        entry = resolve(traffic[role])
        assert callable(entry), (role, traffic[role])
        defaulted = [
            name
            for name, parameter in inspect.signature(entry).parameters.items()
            if parameter.default is not inspect.Parameter.empty
        ]
        assert not defaulted, (path.name, role, traffic[role], "has options", defaulted)
