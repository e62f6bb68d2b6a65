"""BENCHMARK.json and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
configuration's file is the ``file`` of its ``configs`` entry; the traffic
mix is ``traffic/<traffic>.json``; the limits of the comparison are
``limits/<cell>.json``; a per-layer metric's reader is
``metrics/<metric>.py``. A later cell or metric is a new file and a new
entry, never an edit.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout
# traffic settings of the consensus round: stated where nodes exchange,
# and only there
ROUND_SETTINGS = ("scheme", "topology", "local_steps", "eta0", "prox_step",
                  "wire_codec")
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(ValueError):
    pass


def load(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"no {path}")
    return json.loads(path.read_text())


def check_names(bench: dict) -> None:
    """Every name, config, traffic, reduced key and unit in the legal set."""
    names = []
    for c in bench["configs"]:
        names.append(c["name"])
        names.extend(c["reduced"])
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        if not UNIT_RE.match(m["unit"]):
            raise ManifestError(f"unit {m['unit']!r} of {m['name']}")
    for n in names:
        if not NAME_RE.match(n):
            raise ManifestError(f"name {n!r}")
    for group in ("configs", "workloads"):
        seen = [e["name"] for e in bench[group]]
        if len(seen) != len(set(seen)):
            raise ManifestError(f"duplicate name in {group}")
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    if len(metrics) != len(set(metrics)):
        raise ManifestError("duplicate metric name")


def cell(bench: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell's entry with its configuration, traffic and limits loaded."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise ManifestError(f"unknown workload {workload!r}; "
                            f"known: {sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    out = dict(w)
    out["config_entry"] = conf
    out["model"] = json.loads((root / conf["file"]).read_text())
    out["traffic_mix"] = mix = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())
    stated = [k for k in ROUND_SETTINGS if k in mix]
    if mix["nodes"] > 1 and len(stated) < len(ROUND_SETTINGS):
        raise ManifestError(f"traffic {w['traffic']}: a ring needs "
                            f"{ROUND_SETTINGS}")
    if mix["nodes"] == 1 and stated:
        raise ManifestError(f"traffic {w['traffic']}: one node runs no "
                            f"round, so {stated} would be unused")
    out["limits"] = json.loads(
        (HERE / "limits" / f"{workload}.json").read_text())
    out["end_to_end"] = [m for m in bench["end_to_end"]
                         if workload in m.get("workloads", [workload])]
    out["per_layer"] = [m for m in bench["per_layer"]
                        if workload in m.get("workloads", [workload])]
    return out
