"""Finds every piece of the benchmark by name, so that a new configuration,
cell, traffic kind or metric is a new file and never an edit.

    BENCHMARK.json  workloads[*].name -> benchmarks/workloads/<name>.json
                    configs[*].file   -> benchmarks/configs/<config>.json
    cell["kind"]     -> benchmarks/traffic/<kind>.py      (the generator)
    config["family"] -> benchmarks/models/<family>.py     (builds the program)
                        benchmarks/reference/<family>.py  (plain reference)
                        benchmarks/flops/<family>.py      (operations, bytes)
    per_layer[*].name -> benchmarks/metrics/<name>.py     (its reader)
"""
import importlib
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(folder, name):
    """``benchmarks/<folder>/<name>.py`` as a module (names may hold dots,
    which a plain import could not follow)."""
    path = os.path.join(BENCH, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark: no {folder}/{name}.py")
    if "." not in name:
        return importlib.import_module(f"benchmarks.{folder}.{name}")
    modname = "benchmarks." + folder + "." + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    return load_json(ROOT, "BENCHMARK.json")


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json "
                     f"(have {[e['name'] for e in entries]})")


def override(base, over):
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            override(base[k], v)
        else:
            base[k] = v


def cell_and_config(bench, workload, rehearse=None):
    """(BENCHMARK.json's entry, the cell's file, the configuration's file).
    ``rehearse`` names a file whose ``config`` and ``cell`` override the
    sizes (the tests' tiny sizes; never a driver's run)."""
    entry = find(bench["workloads"], workload, "workload")
    cell = load_json(BENCH, "workloads", workload + ".json")
    conf = find(bench["configs"], entry["config"], "configuration")
    cfg = load_json(ROOT, conf["file"])
    if rehearse is not None:
        over = load_json(rehearse)
        override(cfg, over.get("config", {}))
        override(cell, over.get("cell", {}))
    return entry, cell, cfg


def metrics_for(bench, section, workload, reported):
    """The metrics of ``section`` that this cell reports: those that list
    it under ``workloads``, and those without the key whose end-to-end
    metric (``moves``) the cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out
