"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic mix and generator, its metric readers and
the validity its variant promises.

Every piece lives in a file of its own, so a later change adds a
configuration, a traffic mix, a metric or a cell by adding files and
entries, and edits nothing that is there:

  * ``BENCHMARK.json`` (at the root of the checkout): the cells, the
    end-to-end and per-layer metrics, and the configurations' files;
  * ``chipbench/traffic/<traffic>.json``: one traffic mix, read by
    ``chipbench/generator.py``, and beside it, where the mix needs code of
    its own, ``chipbench/traffic/<traffic>.py`` (:func:`generator`);
  * ``chipbench/metrics/<metric>.py``: one metric, end-to-end or per-layer
    (:func:`metric_reader`), a module with ``read(reduction, ctx)`` that
    returns a number, or None
    where the run holds nothing for it to read.  ``reduction`` is the
    trace's (:mod:`chipbench.trace`) in a ``--trace 1`` run and None
    otherwise; ``ctx`` is :class:`chipbench.run.Context`;
  * ``chipbench/promises/<variant>.py``: what a ``QRConfig`` variant
    promises under deaths, as the reference reads it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of the benchmark with everything it names."""

    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple
    per_layer: tuple

    @property
    def shape(self) -> tuple:
        return self.config["rows"], self.config["cols"]

    @property
    def variant(self) -> str:
        return self.config["qr_config"].get("variant", "redundant")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(name: str, root: str) -> Cell:
    """The cell ``name`` of the ``BENCHMARK.json`` under ``root``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "chipbench", "traffic", w["traffic"] + ".json"))
    e2e = tuple(m for m in bench["end_to_end"] if _for_cell(m, name, set()))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"] if _for_cell(m, name, reported))
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic_name=w["traffic"],
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, root: str):
    """The ``read`` function of ``chipbench/metrics/<name>.py``.  A metric
    split by the cells it is reported in, ``<base>.<part>``, is read by
    ``<base>.py`` where it has no file of its own."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        return metric_reader(name.split(".")[0], root)
    return _load_module(path, "chipbench_metric_" + name.replace(".", "_")).read


def generator(traffic: str, root: str):
    """The generator of the mix ``traffic``: ``chipbench/generator.py``,
    with each function that ``chipbench/traffic/<traffic>.py`` defines, if
    that file exists, in its place."""
    from chipbench import generator as general

    path = os.path.join(root, "chipbench", "traffic", traffic + ".py")
    if not os.path.exists(path):
        return general
    mod = _load_module(path, f"chipbench_traffic_{traffic}")
    return types.SimpleNamespace(**{f: getattr(mod, f, getattr(general, f))
                                    for f in general.API})


def promise(variant: str, root: str):
    """The module ``chipbench/promises/<variant>.py``."""
    path = os.path.join(root, "chipbench", "promises", variant + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no promise of the variant {variant!r}: add {path}")
    return _load_module(path, f"chipbench_promise_{variant}")
