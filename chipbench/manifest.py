"""BENCHMARK.json and the files it names.

Everything that belongs to one cell, configuration, traffic mix,
generator, runner, model builder or per-layer metric is a file of its
own, found here BY NAME. A later PR adds files and entries; nothing in
this module (or in run.py) lists a cell.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# a width may never be reduced (the builder's contract)
WIDTH_KEYS = re.compile(
    r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
    r"expansion|experts_per_tok", re.I)


def read_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return read_json(root, "BENCHMARK.json")


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_plugin(root: str, kind: str, name: str):
    """chipbench/<kind>/<name>.py as a module, by file path (names may
    carry dots, which `import` cannot spell)."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} plugin {name!r}: {path} is missing")
    mod_name = f"chipbench_plugin_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered, so that a function of the plugin (the trainer's loop)
    # can be found again by name from another thread or a pickle
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, manifest: dict, workload: str) -> dict:
    """One cell with everything its run needs, all found by name."""
    entry = by_name(manifest["workloads"], workload, "workload")
    cell = read_json(root, f"chipbench/workloads/{workload}.json")
    for k in ("config", "chips", "traffic"):
        if cell[k] != entry[k]:
            raise ValueError(
                f"{workload}: {k} is {cell[k]!r} in its file, {entry[k]!r} in BENCHMARK.json")
    cfg_entry = by_name(manifest["configs"], entry["config"], "config")
    config = read_json(root, cfg_entry["file"])
    traffic = read_json(root, f"chipbench/traffic/{entry['traffic']}.json")
    return {"name": workload, "cell": cell, "config_name": entry["config"],
            "config": config, "traffic": traffic, "chips": entry["chips"]}


def metrics_of(manifest: dict, section: str, workload: str) -> list:
    """Metric entries of `section` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def trace_names(root: str) -> dict:
    """Merge of chipbench/trace_names/*.json, files in name order, a
    later file's entry for the same key wins: {"rules": [(compiled
    regex, class)], "lines": which planes and lines hold what}."""
    d = os.path.join(root, "chipbench", "trace_names")
    patterns: dict = {}
    lines: dict = {}
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            data = read_json(root, f"chipbench/trace_names/{fn}")
            patterns.update(data.get("patterns", {}))
            lines.update(data.get("lines", {}))
    return {"rules": [(re.compile(p), cls) for p, cls in patterns.items()],
            "lines": lines}


def problems(manifest: dict, root: str = ROOT) -> list:
    """Everything wrong with the manifest and the files it names, as
    text; empty when it is well-formed. tests/chipbench holds the repo
    to this, and run.py refuses to start on a non-empty list."""
    bad = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what}: bad name {n!r}")

    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        name_ok(m["name"], "metric")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better is {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source is {m['source']!r}")
        for w in m.get("workloads", ()):
            if w not in cells:
                bad.append(f"{m['name']}: unknown workload {w!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric reads host_clock or device_trace")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']!r} outside (0, 0.1]")
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    if len(set(names)) != len(names):
        bad.append("two metrics share a name")
    for m in manifest["per_layer"]:
        target = e2e.get(m["moves"])
        if target is None:
            bad.append(f"{m['name']}: moves {m['moves']!r}, which is no end-to-end metric")
            continue
        mine = set(m.get("workloads", cells))
        theirs = set(target.get("workloads", cells))
        if not mine <= theirs:
            bad.append(f"{m['name']}: reported in {sorted(mine - theirs)} where "
                       f"{m['moves']} is not")
        try:
            mod = load_plugin(root, "layer_metrics", m["name"])
            if not callable(getattr(mod, "read", None)):
                bad.append(f"{m['name']}: its reader has no read(run)")
        except FileNotFoundError as e:
            bad.append(str(e))
    for c in manifest["configs"]:
        name_ok(c["name"], "config")
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            bad.append(f"{c['name']}: file {c['file']} is outside paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"{c['name']}: file {c['file']} is missing")
        for k in c["reduced"]:
            name_ok(k, f"{c['name']}.reduced")
            if WIDTH_KEYS.search(k):
                bad.append(f"{c['name']}: reduced names a width, {k!r}")
        if c["name"] not in {w["config"] for w in manifest["workloads"]}:
            bad.append(f"config {c['name']} is used by no cell")
    seen = set()
    for w in manifest["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        if w["config"] not in configs:
            bad.append(f"{w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips is {w['chips']!r}")
        if len(w["why"]) > 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"{w['name']}: why is longer than 200 characters or not one line")
        if (w["config"], w["traffic"]) in seen:
            bad.append(f"{w['name']}: its (config, traffic) pair appears twice")
        seen.add((w["config"], w["traffic"]))
        for rel in (f"chipbench/workloads/{w['name']}.json",
                    f"chipbench/traffic/{w['traffic']}.json"):
            if not os.path.isfile(os.path.join(root, rel)):
                bad.append(f"{w['name']}: {rel} is missing")
        if w["config"] in configs and not bad:
            try:
                cell = load_cell(root, manifest, w["name"])
                for kind, key in (("generators", cell["traffic"]["generator"]),
                                  ("runners", cell["config"]["runner"]),
                                  ("model_builders", cell["config"]["model_builder"])):
                    load_plugin(root, kind, key)
            except (KeyError, ValueError, FileNotFoundError) as e:
                bad.append(f"{w['name']}: {e}")
        reported = [m for m in manifest["end_to_end"]
                    if "workloads" not in m or w["name"] in m["workloads"]]
        if len(reported) < 2:
            bad.append(f"{w['name']}: reports no end-to-end metric besides setup_s")
        if not metrics_of(manifest, "per_layer", w["name"]):
            bad.append(f"{w['name']}: reports no per-layer metric")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(manifest['workloads'])}: over 25%")
    if not (isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51):
        bad.append(f"run_seconds {manifest['run_seconds']!r} outside 1..51")
    return bad
