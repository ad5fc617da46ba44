"""An entry point a benchmark cell binds has no option the cell does not set.

A traffic file (``benchmark/traffic/*.json``, read here and never edited)
names the program's entries by dotted path: the chunk ``engine``, and either
the host ``epilogue`` and the ``reconstruct`` or the round ``driver`` that
pairs them itself (PR 34). Each must resolve, and none of its parameters may
carry a default: a default is a second behaviour that no cell measures (the
``draw=`` and ``exact=`` that a second benchmark used to select). Two
parameters are a deployment's and not options: the driver's ``masking``
(PR 39), which a traffic file sets by naming a ``masking_scheme`` and leaves
at ``None`` by naming none, and its ``mesh`` (PR 41), the traffic file's
``mesh`` (``null`` on one chip); cells measure each both ways.
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
#: a driver's parameter that cells set through their traffic file, by the key
#: that sets it: at its default in the cells whose file has no such key
SET_BY_TRAFFIC = {"masking": "masking_scheme", "mesh": "mesh"}


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
            and not (role == "driver" and name in SET_BY_TRAFFIC)
        ]
        assert not defaulted, (path.name, role, traffic[role], "has options", defaulted)


@pytest.mark.parametrize("parameter,key", sorted(SET_BY_TRAFFIC.items()))
def test_a_drivers_parameter_set_by_traffic_is_measured_both_ways(parameter, key):
    """Some cell's traffic file sets the key and some cell's does not (it
    names none, or ``null``: a one-chip file's ``mesh``), both through a driver
    that has the parameter, defaulted to ``None``."""
    by_driver = [json.loads(path.read_text()) for path in TRAFFIC]
    by_driver = [traffic for traffic in by_driver if "driver" in traffic]
    assert {traffic.get(key) is not None for traffic in by_driver} == {True, False}
    for traffic in by_driver:
        default = inspect.signature(resolve(traffic["driver"])).parameters[parameter].default
        assert default is None, (traffic["name"], parameter)
        if isinstance(traffic.get(key), str):  # a dotted path; a mesh is its shape
            assert callable(resolve(traffic[key]))
