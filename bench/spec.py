"""Find a cell's files by name and turn them into what a run needs.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration is ``bench/configs/<config>.json``: the published
``config.json`` keys of the model (as run, with each changed key listed
under ``reduced``), the block that models it (``bench/blocks/<block>/``),
the deployment it stands for, its serving settings (dtype, block size, the
HBM its KV pool gets) and the limit of the check that decides
``correct``. The mix is ``bench/traffic/<traffic>.json``
(``bench/traffic.py`` reads it). Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from types import ModuleType
from typing import Any, Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int = 1

    @property
    def block_size(self) -> int:
        return int(self.config["serving"]["block_size"])

    @property
    def max_len(self) -> int:
        """The longest prompt plus the longest output."""
        return (self.traffic["prompt_len"]["max"]
                + self.traffic["output_len"]["max"])

    def block(self, part: Optional[str] = None) -> ModuleType:
        """The configuration's block package, or one of its modules
        (``"weights"``, ``"reference"``)."""
        name = "blocks." + self.config["block"]
        return importlib.import_module(name + ("." + part if part else ""))

    @property
    def geometry(self):
        return self.block().geometry(self.config, self.max_len)

    @property
    def slots(self) -> int:
        return int(self.traffic["slots"])

    def num_blocks(self) -> int:
        """KV pool blocks: what ``serving.pool_bytes`` holds, but no more
        than every slot at ``max_len``."""
        per_block = self.block_size * self.block().kv_bytes_per_token(
            self.geometry)
        most = self.slots * -(-self.max_len // self.block_size)
        return min(most, int(self.config["serving"]["pool_bytes"]) // per_block)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The workload ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    base = os.path.join(root, "bench")
    return Cell(name=name,
                config=read_json(os.path.join(base, "configs",
                                              w["config"] + ".json")),
                traffic=read_json(os.path.join(base, "traffic",
                                               w["traffic"] + ".json")),
                chips=int(w["chips"]))


def cell_metrics(name: str, trace: bool, root: str = ROOT) -> list:
    """The metrics a run of cell ``name`` reports: its end-to-end metrics
    with ``trace`` off, its per-layer metrics with it on."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    out = []
    for m in bench["per_layer" if trace else "end_to_end"]:
        cells: Optional[list] = m.get("workloads")
        if cells is None or name in cells:
            out.append(m)
    return out
