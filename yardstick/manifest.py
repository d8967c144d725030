"""The benchmark's table of contents: ``BENCHMARK.json`` and the files it
names.

Everything that belongs to one configuration, one cell or one per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``         sizes as they are run, with ``builder``
                                    naming ``builders/<builder>.py``
* ``workloads/<config>.<traffic>.json``  the job's parameters, with ``job``
                                    naming ``jobs/<job>.py``
* ``layer_metrics/<metric>.json``   ``reader`` naming ``readers/<reader>.py``
                                    and the reader's ``params``

A later PR adds a cell, a configuration, a job or a per-layer metric by
adding files and one entry; nothing here lists them.  A name that does not
resolve is an error before anything runs.  No jax in this module: the
parent of a multi-process cell imports it.
"""

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.basename(os.path.dirname(os.path.abspath(__file__)))

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
BENCH_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
              "end_to_end", "per_layer"}


class ManifestError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as exc:
        raise ManifestError("cannot read %s: %s" % (path, exc)) from None
    except ValueError as exc:
        raise ManifestError("%s is not JSON: %s" % (path, exc)) from None


class Manifest:
    def __init__(self, root=ROOT, package=PACKAGE):
        self.root = root
        self.package = package
        self.dir = os.path.join(root, package)
        self.bench = read_json(os.path.join(root, "BENCHMARK.json"))

    # -- lookups -----------------------------------------------------------

    def _entry(self, table, name):
        found = [e for e in self.bench.get(table, ()) if e.get("name") == name]
        if len(found) != 1:
            raise ManifestError("%d entries named %r under %r in "
                                "BENCHMARK.json; it lists: %s"
                                % (len(found), name, table,
                                   [e.get("name") for e in
                                    self.bench.get(table, ())]))
        return found[0]

    def path(self, *parts):
        return os.path.join(self.dir, *parts)

    def module_file(self, kind, name):
        """``<package>/<kind>/<name>.py``; the file must exist."""
        path = self.path(kind, str(name) + ".py")
        if not NAME.match(str(name)) or not os.path.isfile(path):
            raise ManifestError("no %s/%s/%s.py" % (self.package, kind, name))
        return path

    def module(self, kind, name):
        self.module_file(kind, name)
        return importlib.import_module(
            "%s.%s.%s" % (self.package, kind, name))

    def cell(self, name, tiny=False):
        """One cell with everything its files say, as a plain dict.
        ``tiny`` lays each file's ``"tiny"`` block over it: the size of a
        rehearsal on the CPU."""
        entry = self._entry("workloads", name)
        config_entry = self._entry("configs", entry["config"])
        config = read_json(os.path.join(self.root, config_entry["file"]))
        spec = read_json(self.path("workloads", name + ".json"))
        for key in ("job", "chunk_steps"):
            if key not in spec:
                raise ManifestError("workloads/%s.json has no %r"
                                    % (name, key))
        if "builder" not in config:
            raise ManifestError("%s has no 'builder'" % config_entry["file"])
        if tiny:
            config.update(config.get("tiny", {}))
            spec.update(spec.get("tiny", {}))
        return {"name": name, "config_name": entry["config"],
                "traffic": entry["traffic"], "chips": entry["chips"],
                "config": config, "spec": spec, "job": spec["job"],
                "builder": config["builder"]}

    def metrics(self, table, cell_name):
        """The entries of ``end_to_end`` or ``per_layer`` that this cell
        reports, in order."""
        return [m for m in self.bench[table]
                if "workloads" not in m or cell_name in m["workloads"]]

    def layer_metric(self, name):
        """(reader's name, params) of one per-layer metric; the reader's
        file must exist."""
        spec = read_json(self.path("layer_metrics", name + ".json"))
        if "reader" not in spec:
            raise ManifestError("layer_metrics/%s.json has no 'reader'" % name)
        self.module_file("readers", spec["reader"])
        return spec["reader"], spec.get("params", {})

    # -- the whole-manifest check -----------------------------------------

    def problems(self):
        """Everything wrong with the manifest, as a list of sentences;
        empty when every name resolves and the table is well formed."""
        out = []
        bench = self.bench
        if set(bench) != BENCH_KEYS:
            out.append("BENCHMARK.json keys are %s, not %s"
                       % (sorted(bench), sorted(BENCH_KEYS)))
            return out
        names = []
        for table in ("configs", "workloads", "end_to_end", "per_layer"):
            for e in bench[table]:
                names.append(e.get("name"))
                if not NAME.match(str(e.get("name"))):
                    out.append("bad name %r under %s" % (e.get("name"), table))
                if len(e.get("why", "")) > 200:
                    out.append("why of %r is over 200 characters"
                               % e.get("name"))
        for n in set(names):
            if names.count(n) > 1:
                out.append("name %r is used %d times" % (n, names.count(n)))
        for c in bench["configs"]:
            if not c["file"].startswith(
                    tuple(p + "/" for p in bench["paths"])):
                out.append("config file %s lies outside paths" % c["file"])
            elif not os.path.isfile(os.path.join(self.root, c["file"])):
                out.append("config file %s is missing" % c["file"])
            if not any(w["config"] == c["name"] for w in bench["workloads"]):
                out.append("config %r is used by no cell" % c["name"])
        cells = bench["workloads"]
        pairs = [(w["config"], w["traffic"]) for w in cells]
        for w in cells:
            if w["name"] != "%s.%s" % (w["config"], w["traffic"]):
                out.append("cell %r is not named <config>.<traffic>"
                           % w["name"])
            if pairs.count((w["config"], w["traffic"])) > 1:
                out.append("pair %s appears more than once"
                           % ((w["config"], w["traffic"]),))
            if w["chips"] not in (1, 4):
                out.append("cell %r asks for %r chips"
                           % (w["name"], w["chips"]))
            try:
                cell = self.cell(w["name"])
                self.module_file("jobs", cell["job"])
                self.module_file("builders", cell["builder"])
            except ManifestError as exc:
                out.append(str(exc))
        four = sum(1 for w in cells if w["chips"] == 4)
        if four > max(1, len(cells) // 4):
            out.append("%d of %d cells ask for four chips"
                       % (four, len(cells)))
        e2e = {m["name"] for m in bench["end_to_end"]}
        if "setup_s" not in e2e:
            out.append("no setup_s among end_to_end")
        for m in bench["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                out.append("end_to_end %r has source %r"
                           % (m["name"], m["source"]))
            if not 0 < m["bound"] <= 0.1:
                out.append("bound of %r is %r" % (m["name"], m["bound"]))
        for m in bench["per_layer"]:
            if m["source"] not in SOURCES:
                out.append("per_layer %r has source %r"
                           % (m["name"], m["source"]))
            if m["moves"] not in e2e:
                out.append("per_layer %r moves %r, which is no end_to_end "
                           "metric" % (m["name"], m["moves"]))
            try:
                self.layer_metric(m["name"])
            except ManifestError as exc:
                out.append(str(exc))
        known = {w["name"] for w in cells}
        for m in bench["end_to_end"] + bench["per_layer"]:
            for w in m.get("workloads", ()):
                if w not in known:
                    out.append("metric %r lists unknown cell %r"
                               % (m["name"], w))
        for w in cells:
            if not self.metrics("per_layer", w["name"]):
                out.append("cell %r reports no per_layer metric" % w["name"])
        return out


def load(root=ROOT, package=PACKAGE):
    """The manifest, checked: raises ``ManifestError`` naming every
    problem."""
    manifest = Manifest(root, package)
    problems = manifest.problems()
    if problems:
        raise ManifestError("BENCHMARK.json: " + "; ".join(problems))
    return manifest
