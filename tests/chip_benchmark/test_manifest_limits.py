"""The manifest's limits that the driver holds before any run, held here too:
every ``why`` and ``source`` under ``configs`` and ``workloads`` of
``BENCHMARK.json`` is 1-200 printable ASCII characters on one line, every name
fits the 64-character rule, every ``reduced`` key is a name, and every cell
stands in the ``workloads`` of an end-to-end metric it reports beside
``setup_s``. (PR 28 passed its tests and was refused as ``manifest_invalid``
for a ``why`` with a character outside ASCII.)"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
STRINGS = [(f"configs/{c['name']}/{key}", c[key]) for c in MANIFEST["configs"]
           for key in ("why", "source")]
STRINGS += [(f"workloads/{w['name']}/why", w["why"]) for w in MANIFEST["workloads"]]
STRINGS += [(f"per_layer/{m['name']}/layer", m["layer"]) for m in MANIFEST["per_layer"]]
STRINGS += [(f"command/{i}", word) for i, word in enumerate(MANIFEST["command"])]


@pytest.mark.parametrize("where,text", STRINGS, ids=[where for where, _ in STRINGS])
def test_every_free_string_is_1_to_200_printable_ascii_on_one_line(where, text):
    assert isinstance(text, str) and 1 <= len(text) <= 200, (where, len(text))
    assert text.isascii() and text.isprintable(), where  # printable: no tab, no newline


def test_every_name_fits_the_64_character_rule_and_is_unique():
    names = {kind: [entry["name"] for entry in MANIFEST[kind]]
             for kind in ("configs", "workloads", "end_to_end", "per_layer")}
    for kind, listed in names.items():
        assert len(set(listed)) == len(listed), kind
        assert all(NAME.match(name) for name in listed), kind
    assert len(set(names["end_to_end"]) | set(names["per_layer"])) == (
        len(names["end_to_end"]) + len(names["per_layer"]))
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in MANIFEST["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(key) for key in c["reduced"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert len(json.dumps(MANIFEST, indent=2)) <= 64 * 1024


def test_every_cell_stands_in_the_workloads_of_an_end_to_end_metric_it_reports():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for cell in cells:
        reported = [m["name"] for m in MANIFEST["end_to_end"]
                    if m["name"] != "setup_s" and cell in m.get("workloads", cells)]
        assert reported, f"{cell} reports no end-to-end metric beside setup_s"
        per_layer = [m for m in MANIFEST["per_layer"] if cell in m.get("workloads", cells)]
        assert per_layer and all(m["moves"] in reported for m in per_layer), cell
