"""The Graph500 SCALE 26 configuration: its keys, its cut (the stream's head),
and a tiny run of its cell against the plain reference, on a stream whose
chunks keep hundreds of winners."""

import json
import os
import time

import numpy as np

from chipbench import graphgen, run
from chipbench.drivers import stream
from chipbench.tests.tiny import ROOT, tiny_cell

CELL = "g500-26-chunked-bin"
SEED = 2**31 + 101
INITIATOR = [0.57, 0.19, 0.19, 0.05]
METRICS = {
    "chunked_update_ns_per_edge.n26",
    "chunked_update_roofline.n26",
    "finalize_ms_per_pass.n26",
    "dispatches_per_medge.n26",
    "source_read_ms_per_medge.n26",
    "device_idle_pct.n26",
}


def _conf():
    with open(os.path.join(ROOT, "chipbench", "configs", "graph500-26-chunked.json")) as f:
        return json.load(f)


def test_configuration_keys_agree():
    conf = _conf()
    n = 1 << 26
    assert conf["scale"] == 26 and conf["n"] == n
    assert conf["edgefactor"] == 2 and conf["m"] == 2 * n
    assert conf["published"] == {"edgefactor": 16, "m": 16 * n}
    assert "edgefactor" in conf["reduced"]
    assert f"{12 * n:,} bytes" in conf["state_bytes"]
    assert conf["initiator"] == INITIATOR
    assert conf["cluster"] == {"backend": "chunked", "v_max": 64, "chunk": 1024}
    assert conf["guarantee"]["rule"] == "jacobi" and conf["guarantee"]["chunk"] == 1024
    assert conf["control"]["cluster"] == {"chunk": 2048}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["graph500-26-chunked"]
    assert entry["reduced"] == conf["reduced"]


def test_cut_is_the_stream_head(monkeypatch):
    # blocks are keyed by their index, so a smaller edge factor keeps the
    # first rows of the published stream bit for bit
    monkeypatch.setattr(graphgen, "BLOCK_ROWS", 1 << 10)
    head = graphgen.kronecker_edges(12, 2, INITIATOR, SEED)
    full = graphgen.kronecker_edges(12, 16, INITIATOR, SEED)
    assert head.shape == (2 << 12, 2)
    assert np.array_equal(head, full[: 2 << 12])


def _winners_per_chunk(edges, n, v_max, chunk):
    """Nodes moved by each chunk under the Jacobi rule (first row in stream
    order wins), replayed in numpy."""
    d = np.zeros(n, np.int64)
    c = np.arange(n, dtype=np.int64)
    v = np.zeros(n, np.int64)
    out = []
    for s in range(0, edges.shape[0], chunk):
        i, j = edges[s : s + chunk, 0].astype(np.int64), edges[s : s + chunk, 1].astype(np.int64)
        live = i != j
        i, j = i[live], j[live]
        np.add.at(d, i, 1)
        np.add.at(d, j, 1)
        ci, cj = c[i], c[j]
        np.add.at(v, ci, 1)
        np.add.at(v, cj, 1)
        vi, vj = v[ci], v[cj]
        ok = (vi <= v_max) & (vj <= v_max)
        i_joins = vi <= vj
        mover = np.where(i_joins, i, j)[ok]
        target = np.where(i_joins, cj, ci)[ok]
        source = np.where(i_joins, ci, cj)[ok]
        _, first = np.unique(mover, return_index=True)
        mover, target, source = mover[first], target[first], source[first]
        np.add.at(v, target, d[mover])
        np.subtract.at(v, source, d[mover])
        c[mover] = target
        out.append(mover.size)
    return np.array(out)


def test_tiny_run_is_correct_and_its_control_is_not():
    cell = tiny_cell(CELL, scale=12, edgefactor=2, batch=2048)
    conf = cell["config_data"]
    assert conf["cluster"]["backend"] == "chunked"
    edges = stream.make_edges(conf, SEED)
    wins = _winners_per_chunk(edges, conf["n"], conf["cluster"]["v_max"], 1024)
    # a sparse head: the chunk-wide pass (past 512 winners) and several
    # rounds of 32 both run
    assert (wins > 512).any()
    assert ((wins > 32) & (wins <= 512)).any()

    def go(override=None):
        return stream.run(cell, seed=SEED, seconds=0.3, trace=False,
                          t_start=time.perf_counter(), override=override)

    rec = go()
    assert rec["correct"] and rec["failed"] == 0
    assert rec["checks"]["label_mismatches"]["value"] == 0
    assert rec["checks"]["edges_seen_gap"]["value"] == 0
    bad = go(conf["control"]["cluster"])
    assert not bad["correct"]
    assert bad["checks"]["label_mismatches"]["value"] > 0


def test_cell_reads_the_new_metrics():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "random-bin"
    assert {m["name"] for m in cell["per_layer"]} == METRICS
    assert {m["name"] for m in cell["end_to_end"]} == {"edges_per_s", "setup_s"}
    for name in METRICS:
        assert callable(run.reader(name))
