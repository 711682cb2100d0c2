"""Find a cell's files by the names in BENCHMARK.json: the cell, its
configuration, its traffic mix, its family modules and its metric readers.
Nothing here knows a cell, a configuration, a mix or a metric by name."""
import dataclasses
import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def module(*parts):
    """Import a file under perfbench/ by its path: metric readers and family
    modules are found by name, and a name may hold a dot."""
    path = os.path.join(BENCH_DIR, *parts)
    name = "perfbench_" + re.sub(r"\W", "_", "_".join(parts))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    params: dict      # workloads/<cell>.json: what belongs to this pairing
    config: dict      # configs/<config>.json, as it is run
    mix: dict         # traffic/<mix>.json
    end_to_end: list  # this cell's metric entries from BENCHMARK.json
    per_layer: list

    @property
    def kind(self):
        return self.mix["kind"]


def cell(name, rehearsal=False):
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(REPO, conf["file"])) as f:
        config = json.load(f)
    mix = load_json("traffic", entry["traffic"] + ".json")
    params = load_json("workloads", name + ".json")
    if rehearsal:
        # tiny sizes for the tests on the CPU: each file carries its own
        config, mix, params = (overlay(d, d.get("rehearsal", {}))
                               for d in (config, mix, params))

    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or name in m["workloads"]]

    return Cell(name=name, chips=entry["chips"], why=entry["why"],
                params=params, config=config, mix=mix,
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def family(config):
    """(builder module, reference module) named by the configuration."""
    fam = "perfbench.models." + config["family"]
    return importlib.import_module(fam), importlib.import_module(fam + "_ref")


def reader(metric_name):
    return module("metrics", metric_name + ".py").read
