"""A cell of the benchmark cut to a size a CPU test can run."""

from __future__ import annotations

import copy

from chipbench import run

ROOT = run.ROOT


def tiny_cell(name: str, scale: int = 11, edgefactor: int = 16, batch: int = 4096) -> dict:
    """The cell ``name`` as ``BENCHMARK.json`` has it, with the graph cut to
    ``2**scale`` nodes and ``edgefactor * 2**scale`` edges and the batch to
    ``batch`` rows."""
    cell = copy.deepcopy(run.load_cell(name))
    conf = cell["config_data"]
    conf["scale"], conf["edgefactor"] = scale, edgefactor
    conf["n"], conf["m"] = 1 << scale, edgefactor << scale
    conf["cluster"]["batch_edges"] = batch
    return cell
