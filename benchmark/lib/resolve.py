"""Finding a cell's files by the names in ``BENCHMARK.json``.

A later PR adds a cell by adding entries to ``BENCHMARK.json`` and files
beside the ones here; it edits none. Nothing in this module knows the name
of a configuration, a traffic mix, a family or a metric.

    <root>/BENCHMARK.json
    <root>/<bench>/configs/<config>.json           (the entry's ``file``)
    <root>/<bench>/traffic/<traffic>.json
    <root>/<bench>/families/<family>.py            (traffic's ``family``)
    <root>/<bench>/layer_metrics/<metric>.py
"""

from __future__ import annotations

import importlib.util
import json
import os


class NoSuchEntry(Exception):
    pass


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise NoSuchEntry(
        f"no {what} named {name!r} in BENCHMARK.json "
        f"(have: {sorted(e['name'] for e in entries)})"
    )


def find_workload(manifest: dict, name: str) -> dict:
    return _by_name(manifest["workloads"], name, "workload")


def load_config(root: str, manifest: dict, name: str) -> dict:
    entry = _by_name(manifest["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_traffic(bench_dir: str, name: str) -> dict:
    """A traffic file may say ``"extends": "<other traffic>"``: it is then
    that file with this one's top-level keys laid over it (one level, no
    chains of more than 8)."""
    chain, seen = [], set()
    while name is not None:
        if name in seen or len(chain) >= 8:
            raise ValueError(f"traffic 'extends' loops or is too deep: {name}")
        seen.add(name)
        with open(os.path.join(bench_dir, "traffic", name + ".json")) as f:
            chain.append(json.load(f))
        name = chain[-1].get("extends")
    merged = {}
    for layer in reversed(chain):
        merged.update(layer)
    merged.pop("extends", None)
    return merged


def _load_module(path: str, modname: str):
    if not os.path.exists(path):
        raise NoSuchEntry(f"no file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(bench_dir: str, name: str):
    """``families/<name>.py`` with ``build(config, traffic)``."""
    return _load_module(
        os.path.join(bench_dir, "families", name + ".py"),
        f"benchmark_family_{name}",
    )


def load_layer_metric(bench_dir: str, name: str):
    """``layer_metrics/<name>.py`` with ``read(run)``."""
    return _load_module(
        os.path.join(bench_dir, "layer_metrics", name + ".py"),
        f"benchmark_layer_metric_{name}",
    )


def metrics_for(manifest: dict, group: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that exist in this cell
    (an entry with no ``workloads`` key exists in every cell)."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


# ``step_kwargs`` values that name an object of the framework, and where
# the harness looks the name up. Every other value is passed as it stands.
NAMED_STEP_KWARGS = {
    "compression": "Compression",
    "gather_compression": "Compression",
}


def resolve_step_kwargs(step_kwargs: dict, framework) -> dict:
    out = {}
    for key, value in step_kwargs.items():
        holder = NAMED_STEP_KWARGS.get(key)
        if holder is not None and isinstance(value, str):
            value = getattr(getattr(framework, holder), value)
        out[key] = value
    return out


def resolve_optimizer(spec: dict, optax):
    """``{"name": ..., "args": {...}}`` -> ``optax.<name>(**args)``. An
    argument given as ``{"schedule": <name>, "args": {...}}`` becomes
    ``optax.<name>(**args)`` first, so a warm-up or a decay is data too."""
    args = {
        key: getattr(optax, value["schedule"])(**value.get("args", {}))
        if isinstance(value, dict) and "schedule" in value else value
        for key, value in spec.get("args", {}).items()
    }
    return getattr(optax, spec["name"])(**args)
