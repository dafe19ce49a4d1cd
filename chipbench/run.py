#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell's entry in ``BENCHMARK.json``
names its configuration file; the cell's traffic file under
``chipbench/traffic/`` names the driver (``chipbench/drivers/<driver>.py``)
and the input it reads.  With ``--trace 0`` the last line of standard
output carries the cell's end-to-end metrics; with ``--trace 1`` the
window is traced and it carries the per-layer metrics, each read by
``chipbench/metrics/<metric>.py``.  Every run checks the window's answers
against a plain reference and prints each compared number beside its
limit, as the last lines of standard error and as the last key of the
result.

The run fails, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _paths() -> None:
    # run as a script, this directory heads sys.path; its modules are
    # imported as ``chipbench.*`` instead
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration and traffic files read."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cell["config_data"] = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        cell["traffic_data"] = json.load(f)
    cell["end_to_end"] = [
        m for m in bench["end_to_end"] if name in m.get("workloads", [name])
    ]
    cell["per_layer"] = [
        m for m in bench["per_layer"] if name in m.get("workloads", [name])
    ]
    return cell


def require_chips(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero off a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"cell needs {chips} chips, JAX found {len(devices)}", file=sys.stderr)
        raise SystemExit(3)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def reader(name: str):
    """The ``read`` function of ``chipbench/metrics/<name>.py`` (a metric's
    name may hold dots, so the file is loaded by its path)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}",
        os.path.join(HERE, "metrics", name + ".py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(cell: dict, record: dict, trace: bool) -> dict:
    """End-to-end metrics from the record, or per-layer metrics from their
    readers; a reader that finds nothing to read leaves its metric out."""
    out = {}
    if not trace:
        for m in cell["end_to_end"]:
            out[m["name"]] = {"value": record["end_to_end"][m["name"]], "unit": m["unit"]}
        return out
    for m in cell["per_layer"]:
        value = reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _paths()
    cell = load_cell(args.workload)
    device = require_chips(int(cell["chips"]))
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['traffic_data']['driver']}"
    )
    record = driver.run(
        cell,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        t_start=T_START,
    )
    device.update(record["device"])
    result = {
        "correct": record["correct"],
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": read_metrics(cell, record, bool(args.trace)),
        "device": device,
    }
    if args.trace:
        result["breakdown"] = record["breakdown"]
    result["checks"] = record["checks"]
    for name, c in record["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
