"""``BENCHMARK.json`` and the files it names. Everything that belongs to one
configuration, one traffic mix, one traffic kind, one builder or one
per-layer metric is a file found by its name, so a later PR adds cells and
metrics without editing anything that is here."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = None) -> dict:
    return load_json(os.path.join(root or ROOT, "BENCHMARK.json"))


def module_from(subdir: str, name: str):
    """``chipbench/<subdir>/<name>.py`` as a module; the name may hold dots
    (``zoo.Bert.large``, ``device_idle_share.train``)."""
    path = os.path.join(HERE, subdir, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no chipbench/{subdir}/{name}.py: add that file to add "
            f"{name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{subdir}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration and traffic mix."""

    def __init__(self, manifest: dict, name: str, root: str = None):
        root = root or ROOT
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.manifest = manifest
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        conf = {c["name"]: c for c in manifest["configs"]}[
            self.spec["config"]]
        self.cfg = load_json(os.path.join(root, conf["file"]))
        self.mix = load_json(os.path.join(HERE, "traffic",
                                          self.spec["traffic"] + ".json"))

    def _lists(self, group: str):
        for m in self.manifest[group]:
            if "workloads" not in m or self.name in m["workloads"]:
                yield m

    def end_to_end(self):
        return list(self._lists("end_to_end"))

    def per_layer(self):
        """Per-layer metrics of this cell: those that list it, and those
        without a list whose end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self._lists("per_layer") if m["moves"] in mine]
