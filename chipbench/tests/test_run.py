"""``run.py`` as the benchmark's command, and ``BENCHMARK.json`` against
the shape the benchmark's contract sets."""

import json
import os
import re
import subprocess
import sys

import pytest

from chipbench.tests.tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_run_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "g500-chunked-bin",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_json_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]]
    names += [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("chipbench/") and len(c["why"]) <= 200
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json"))
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".py"))
        assert set(m.get("workloads", [])) <= {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("name", ["g500-chunked-bin", "g500-pallas-bin"])
def test_every_cell_reports_setup_another_metric_and_a_layer(name):
    from chipbench import run

    cell = run.load_cell(name)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert "edges_per_s" in e2e
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert m["moves"] in e2e  # a layer moves a metric its cell reports
        assert callable(run.reader(m["name"]))


@pytest.mark.parametrize("trace", [0, 1])
def test_result_line(monkeypatch, capsys, trace):
    """A whole run through ``run.main`` at a tiny size, past the look for a
    chip: the last line of standard output is the result, with every key
    the contract names, and the compared numbers come last."""
    from chipbench import run
    from chipbench.tests.tiny import tiny_cell

    cell = tiny_cell("g500-chunked-bin", scale=11, edgefactor=8, batch=2048)
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    monkeypatch.setattr(run, "require_chips", lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1})
    assert run.main(["--workload", "g500-chunked-bin", "--seed", str(2**31 + 9),
                     "--seconds", "0.2", "--trace", str(trace)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks" and out["correct"] is True
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert "memory_peak_bytes" in out["device"]
    want = cell["per_layer"] if trace else cell["end_to_end"]
    got = set(out["metrics"])
    if trace:  # no device planes off a TPU: the device readers find nothing
        assert got == {"dispatches_per_medge", "source_read_ms_per_medge", "finalize_ms_per_pass"}
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert got == {m["name"] for m in want}
