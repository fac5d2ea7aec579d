"""BENCHMARK.json and the files found by the names in it.

Everything that belongs to one cell, configuration, traffic mix, family,
path or per-layer metric is one file under ``benchmarks/`` found by name,
so a later PR adds files and one entry and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)

# the driver's character rules (builder's contract)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def _one(entries: list, name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SpecError(f"{what} {name!r}: {len(found)} entries in "
                        f"BENCHMARK.json (known: "
                        f"{[e['name'] for e in entries]})")
    return found[0]


def resolve(bench: dict, workload: str) -> dict:
    """The cell's entry, its configuration file and its traffic file."""
    cell = _one(bench["workloads"], workload, "workload")
    cfg_entry = _one(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(CHECKOUT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    return {"cell": cell, "config": config, "traffic": traffic}


def with_rehearsal(doc: dict) -> dict:
    """The labelled CPU toy of a configuration or traffic file: its
    ``rehearsal`` overrides applied on top of the real values."""
    out = {k: v for k, v in doc.items() if k != "rehearsal"}
    out.update(doc.get("rehearsal", {}))
    return out


def fixed(doc: dict, **computed) -> None:
    """Keys of a configuration file whose value the model file computes
    whatever the file says: a file that states another value would
    describe something that is not run, and is refused."""
    for key, value in computed.items():
        if doc.get(key) != value:
            raise SpecError(f"{doc.get('name')}: {key}={doc.get(key)!r}, "
                            f"but the model file computes {value!r}")


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (kind: families,
    paths, layer_metrics)."""
    if not NAME_RE.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, section: str, workload: str) -> list:
    """Entries of ``end_to_end`` / ``per_layer`` this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]
