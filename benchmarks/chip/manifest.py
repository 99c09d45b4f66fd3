"""Reads ``BENCHMARK.json`` and the files it names, and checks them
against the rules the driver refuses a manifest for. Everything about a
cell comes from here: no cell, configuration, mix or metric is named in
code."""

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def repo_root(bench_dir):
    """The benchmark lives at <root>/benchmarks/chip."""
    return os.path.dirname(os.path.dirname(os.path.abspath(bench_dir)))


def _json(path):
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root):
        self.root = os.path.abspath(root)
        self.doc = _json(os.path.join(self.root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(self.root, self.doc["paths"][0])

    def path(self, *parts):
        return os.path.join(self.bench_dir, *parts)

    def workload(self, name):
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def cell(self, name):
        """The workload's entry, its cell file, configuration and mix."""
        w = self.workload(name)
        cell = _json(self.path("cells", f"{name}.json"))
        cfg_entry = next(c for c in self.doc["configs"]
                         if c["name"] == w["config"])
        config = _json(os.path.join(self.root, cfg_entry["file"]))
        mix_file = self.path("mixes", f"{w['traffic']}.json")
        return w, cell, config, mix_file

    def metrics_of(self, name, group):
        """The ``end_to_end`` or ``per_layer`` metrics that cell reports."""
        return [m for m in self.doc[group]
                if "workloads" not in m or name in m["workloads"]]

    def validate(self):
        """Raises ValueError naming the first broken rule."""
        doc = self.doc

        def need(ok, msg):
            if not ok:
                raise ValueError(f"BENCHMARK.json: {msg}")

        e2e = {m["name"]: m for m in doc["end_to_end"]}
        need("setup_s" in e2e, "no setup_s")
        names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
        need(len(set(names)) == len(names), "two metrics share a name")
        for m in doc["end_to_end"] + doc["per_layer"]:
            need(NAME.match(m["name"]), f"metric name {m['name']!r}")
            need(UNIT.match(m["unit"]), f"unit {m['unit']!r}")
            need(m["better"] in ("lower", "higher"), f"better of {m['name']}")
            need(m["source"] in SOURCES, f"source of {m['name']}")
        for m in doc["end_to_end"]:
            need(m["source"] in ("host_clock", "device_trace"),
                 f"end-to-end {m['name']} from {m['source']}")
            need(0 < m["bound"] <= 0.1, f"bound of {m['name']}")
        cells = [w["name"] for w in doc["workloads"]]
        need(len(set(cells)) == len(cells), "two cells share a name")
        configs = {c["name"] for c in doc["configs"]}
        used = set()
        for w in doc["workloads"]:
            for key in ("name", "config", "traffic"):
                need(NAME.match(w[key]), f"{key} {w[key]!r}")
            need(w["chips"] in (1, 4), f"chips of {w['name']}")
            need(w["config"] in configs, f"{w['name']}: unknown config")
            need(0 < len(w["why"]) <= 200 and "\n" not in w["why"],
                 f"why of {w['name']}")
            used.add(w["config"])
            _, cell, _, mix_file = self.cell(w["name"])
            need(os.path.exists(mix_file), f"no mix file {mix_file}")
            placement = self.path("placements", f"{cell['placement']}.py")
            need(os.path.exists(placement), f"no placement {placement}")
            reported = {m["name"] for m in
                        self.metrics_of(w["name"], "end_to_end")}
            need(len(reported) >= 2, f"{w['name']} reports only setup_s")
            layer = self.metrics_of(w["name"], "per_layer")
            need(layer, f"{w['name']} reports no per-layer metric")
            for m in layer:
                need(m["moves"] in reported,
                     f"{m['name']} moves {m['moves']}, which "
                     f"{w['name']} does not report")
        need(used == configs, "a configuration has no cell")
        for c in doc["configs"]:
            need(NAME.match(c["name"]), f"config name {c['name']!r}")
            need(c["file"].startswith(doc["paths"][0] + "/"),
                 f"{c['file']} outside paths")
            need(len(c["reduced"]) <= 16 and
                 all(NAME.match(k) for k in c["reduced"]),
                 f"reduced of {c['name']}")
        for m in doc["per_layer"]:
            need(m["moves"] in e2e, f"{m['name']} moves {m['moves']}")
            need(os.path.exists(
                self.path("layer_metrics", f"{m['name']}.py")),
                f"no reader layer_metrics/{m['name']}.py")
            for cell_name in m.get("workloads", []):
                need(cell_name in cells,
                     f"{m['name']} lists unknown cell {cell_name}")
        four = sum(w["chips"] == 4 for w in doc["workloads"])
        need(four <= max(1, len(cells) // 4), "too many four-chip cells")
        return True
