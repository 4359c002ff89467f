"""A cell of ``BENCHMARK.json`` and the files it names, found by name:
``configs`` entry -> its ``file``; traffic -> ``traffic/<name>.json``;
metric -> ``metrics/<name>.py``; correctness limits ->
``limits/<cell>.json``. Imports no JAX."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, bench: dict | None = None) -> Cell:
    """A cell of ``BENCHMARK.json``, or of ``bench``: a dict of the same
    keys whose ``limits`` holds each of its cells' limits."""
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        limits=bench["limits"][name] if "limits" in bench else
        json.loads((HERE / "limits" / f"{name}.json").read_text()))


def arch_config(conf: dict):
    """The program's ``ArchConfig`` from a configuration file: its fields
    are taken by name, other keys (source, assumed, notes) are left."""
    import dataclasses
    import jax.numpy as jnp
    from repro.models import ArchConfig
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {k: v for k, v in conf.items() if k in names}
    if "dtype" in kw:
        kw["dtype"] = jnp.dtype(kw["dtype"])
    for k, v in kw.items():
        if isinstance(v, list):
            kw[k] = tuple(v)
    return ArchConfig(**kw)


def metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
