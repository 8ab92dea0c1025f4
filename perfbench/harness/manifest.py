"""BENCHMARK.json and the files it names.

A cell names a configuration and a traffic mix; a metric names itself.  The
harness finds each by that name in a directory of its own kind:

    configs/<config>.json        the configuration's sizes (the manifest's
                                 ``file``), with ``entry`` = its builder
    traffic/<traffic>.json       the mix's parameters, with ``job`` and ``kind``
    builders/<module>.py         ``entry`` "<module>:<function>"
    jobs/<job>.py                ``run(ctx)`` — drives one run of a cell
    kinds/<kind>.py              ``make(params, seed, ...)`` — the generator
    end_to_end/<metric>.py       ``read(run)`` — one end-to-end metric
    layer_metrics/<metric>.py    ``read(run)`` — one per-layer metric

Nothing is registered in a list inside a Python file.  ``roots`` is searched
in order, so a test (or a later PR's directory) can add files without
touching the ones that are there.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


class Manifest:
    """The parsed manifest plus the search roots for the files it names."""

    def __init__(self, path: Optional[str] = None,
                 roots: Optional[Sequence[str]] = None):
        self.path = os.path.abspath(path or os.path.join(REPO,
                                                         "BENCHMARK.json"))
        self.base = os.path.dirname(self.path)
        with open(self.path) as f:
            self.data: Dict[str, Any] = json.load(f)
        self.roots: List[str] = [os.path.abspath(r) for r in (roots or [])]
        if HERE not in self.roots:
            self.roots.append(HERE)

    # -- lookups -----------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"no workload {name!r} in {self.path}")

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no config {name!r} in {self.path}")

    def metrics_for(self, section: str, cell: str) -> List[Dict[str, Any]]:
        """The metrics of ``end_to_end`` / ``per_layer`` that ``cell``
        reports: those without a ``workloads`` key, and those that list it."""
        return [m for m in self.data[section]
                if "workloads" not in m or cell in m["workloads"]]

    # -- files ---------------------------------------------------------------
    def find(self, kind_dir: str, filename: str) -> str:
        for root in self.roots:
            p = os.path.join(root, kind_dir, filename)
            if os.path.isfile(p):
                return p
        raise ManifestError(
            f"{kind_dir}/{filename} not found under {self.roots}")

    def load_config(self, name: str) -> Dict[str, Any]:
        entry = self.config_entry(name)
        with open(os.path.join(self.base, entry["file"])) as f:
            return json.load(f)

    def load_traffic(self, name: str) -> Dict[str, Any]:
        with open(self.find("traffic", name + ".json")) as f:
            return json.load(f)

    def load_module(self, kind_dir: str, name: str):
        """Import ``<root>/<kind_dir>/<name>.py`` by path.  The name may hold
        dots and dashes (``decode_batch_occupancy.chat``), so it is never an
        import statement."""
        path = self.find(kind_dir, name + ".py")
        mod_name = "perfbench_plugin_" + re.sub(r"[^A-Za-z0-9_]", "_",
                                                f"{kind_dir}_{name}")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def load_entry(self, entry: str):
        """``"<module>:<function>"`` under ``builders/``."""
        module, _, func = entry.partition(":")
        if not func:
            raise ManifestError(f"entry {entry!r} is not '<module>:<function>'")
        return getattr(self.load_module("builders", module), func)


def validate(m: Manifest) -> List[str]:
    """The rules of the benchmark's contract that a file can be held to
    without a chip.  Returns the faults found (empty = none)."""
    d, bad = m.data, []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(d) != want:
        bad.append(f"keys {sorted(d)} != {sorted(want)}")
        return bad

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what}: bad name {n!r}")

    def line_ok(s, what):
        if (not isinstance(s, str) or not 1 <= len(s) <= 200
                or "\n" in s or "\t" in s):
            bad.append(f"{what}: not 1-200 characters on one line")

    def under_paths(p):
        p = os.path.normpath(p)
        if p.startswith("..") or os.path.isabs(p):
            return False
        return any(os.path.normpath(q) == "." or p.startswith(
            os.path.normpath(q) + os.sep) for q in d["paths"])

    if not 1 <= len(d["paths"]) <= 16:
        bad.append("paths: 1 to 16 directories")
    for p in d["paths"]:
        if (not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
                or p.startswith("/") or ".." in p.split("/")):
            bad.append(f"paths: bad path {p!r}")
    if not 1 <= len(d["command"]) <= 32:
        bad.append("command: 1 to 32 words")
    for w in d["command"]:
        line_ok(w, "command")
        if w.startswith("/") or ".." in w.split("/"):
            bad.append(f"command: {w!r} leaves the repo")
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        bad.append("run_seconds: whole number from 1 to 51")

    cfg_names, files = set(), set()
    if not 1 <= len(d["configs"]) <= 24:
        bad.append("configs: 1 to 24")
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        if c["name"] in cfg_names:
            bad.append(f"config {c['name']} twice")
        cfg_names.add(c["name"])
        if not under_paths(c["file"]) or c["file"] in files:
            bad.append(f"config {c['name']}: file {c['file']!r} not under "
                       f"paths, or shared")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(m.base, c["file"])):
            bad.append(f"config {c['name']}: {c['file']} does not exist")
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']}: more than 16 reduced keys")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
            if (k.endswith("_dim") or k.endswith("_rank")
                    or re.search(r"hidden_size|intermediate|head_size|"
                                 r"expansion|experts_per_tok", k)):
                bad.append(f"config {c['name']}: reduced names a width {k!r}")

    cells, pairs, used_cfg = {}, set(), set()
    if not 1 <= len(d["workloads"]) <= 24:
        bad.append("workloads: 1 to 24")
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["name"] in cells:
            bad.append(f"workload {w['name']} twice")
        cells[w["name"]] = w
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"pair {w['config']} x {w['traffic']} twice")
        pairs.add((w["config"], w["traffic"]))
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        used_cfg.add(w["config"])
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for four chips")
    if cfg_names - used_cfg:
        bad.append(f"configs used by no cell: {sorted(cfg_names - used_cfg)}")

    e2e_by_cell = {c: set() for c in cells}
    seen = set()
    for section, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                          "source"}),
                          ("per_layer", {"name", "unit", "better", "source",
                                         "layer", "moves"})):
        lim = 16 if section == "end_to_end" else 128
        if not 1 <= len(d[section]) <= lim:
            bad.append(f"{section}: 1 to {lim} metrics")
        for x in d[section]:
            if set(x) - {"workloads"} != keys:
                bad.append(f"{section} {x.get('name')}: keys {sorted(x)}")
                continue
            name_ok(x["name"], section)
            if x["name"] in seen:
                bad.append(f"metric {x['name']} twice")
            seen.add(x["name"])
            if not UNIT_RE.match(x["unit"]):
                bad.append(f"{x['name']}: unit {x['unit']!r}")
            if x["better"] not in ("lower", "higher"):
                bad.append(f"{x['name']}: better {x['better']!r}")
            if x["source"] not in SOURCES:
                bad.append(f"{x['name']}: source {x['source']!r}")
            for c in x.get("workloads", ()):
                if c not in cells:
                    bad.append(f"{x['name']}: unknown workload {c!r}")
            where = [c for c in x.get("workloads", cells) if c in cells]
            if section == "end_to_end":
                if x["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"{x['name']}: end-to-end source")
                if not (isinstance(x["bound"], (int, float))
                        and 0 < x["bound"] <= 0.1):
                    bad.append(f"{x['name']}: bound {x['bound']!r}")
                for c in where:
                    e2e_by_cell[c].add(x["name"])
            else:
                line_ok(x["layer"], f"{x['name']} layer")
                for c in where:
                    if x["moves"] not in e2e_by_cell[c]:
                        bad.append(f"{x['name']} moves {x['moves']}, which "
                                   f"cell {c} does not report")
    layer_cells = {c for x in d["per_layer"]
                   for c in x.get("workloads", cells)}
    for c in cells:
        if "setup_s" not in e2e_by_cell[c] or len(e2e_by_cell[c]) < 2:
            bad.append(f"cell {c}: needs setup_s and one more end-to-end")
        if c not in layer_cells:
            bad.append(f"cell {c}: no per-layer metric")
    if os.path.getsize(m.path) > 64 * 1024:
        bad.append("BENCHMARK.json over 64 KiB")
    return bad
