"""A cell's files, found by the names in ``BENCHMARK.json``.

A configuration file (``configs/<name>.json``) holds a deployment's
shapes and the guarantees it states; its ``kind`` names the module
under ``kinds/`` that knows the shape: how the schedule is made from
the seed, how the program's store and workloads are built, and the
plain reference that holds a run to the guarantees
(``kinds/__init__.py`` has the interface). A traffic file
(``traffic/<name>.json``) says how the schedule is played. Nothing here
knows a cell, and nothing here imports the program.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
#: the kind of a configuration file that names none
DEFAULT_KIND = "flat"


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def kind_of(cfg: dict):
    """The module of the configuration's kind."""
    return importlib.import_module(
        f"benchmark.kinds.{cfg.get('kind', DEFAULT_KIND)}")


def load_config(name: str) -> dict:
    cfg = load_json("configs", f"{name}.json")
    return kind_of(cfg).load(cfg)


def scaled(cfg: dict, cohorts: int | None, cqs_per_cohort: int | None,
           count_div: int) -> dict:
    """A smaller copy for the CPU rehearsal and the tests, cut as the
    configuration's kind cuts it; a measurement run never calls this."""
    return kind_of(cfg).scaled(cfg, cohorts, cqs_per_cohort, count_div)


def schedule(cfg: dict, seed: int) -> list:
    """The arrival schedule of the configuration's kind, sorted by due
    time."""
    return kind_of(cfg).schedule(cfg, seed)


def load_traffic(name: str) -> dict:
    traffic = load_json("traffic", f"{name}.json")
    if traffic.get("kind") != "replay":
        raise ValueError(
            f"traffic/{name}.json: kind {traffic.get('kind')!r} has no "
            "generator (known: replay)")
    return traffic
