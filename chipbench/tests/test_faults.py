"""A run with the timed path broken underneath comes out not correct; a
sound run and each configuration's control are told apart.

These drive the whole of a run but the look for a chip, on the CPU at a
tiny size.  One chip holds the whole state, so there is no exchange
between chips to leave out."""

import time

import numpy as np
import pytest

from chipbench.drivers import stream
from chipbench.tests.tiny import tiny_cell

SEED = 2**31 + 101

CELLS = {
    "g500-chunked-bin": dict(scale=11, edgefactor=12, batch=2048),
    "g500-pallas-bin": dict(scale=10, edgefactor=4, batch=1024),
}
UPDATE = {"chunked": "chunked_update", "pallas": "pallas_update"}


def _run(cell, seconds=0.3, override=None):
    return stream.run(
        cell, seed=SEED, seconds=seconds, trace=False, t_start=time.perf_counter(),
        override=override,
    )


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    rec = _run(tiny_cell(name, **CELLS[name]))
    assert rec["correct"] and rec["failed"] == 0
    assert rec["checks"]["label_mismatches"]["value"] == 0
    assert rec["checks"]["edges_seen_gap"]["value"] == 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(name):
    cell = tiny_cell(name, **CELLS[name])
    rec = _run(cell, override=cell["config_data"]["control"]["cluster"])
    assert not rec["correct"]
    assert rec["checks"]["label_mismatches"]["value"] > 0


def _break_update(monkeypatch, backend, how):
    from repro.cluster import backends

    name = UPDATE[backend]
    orig = getattr(backends, name)

    def unchanged(state, edges, v_max, chunk=1024):
        return state

    def half_batch(state, edges, v_max, chunk=1024):
        return orig(state, edges[: edges.shape[0] // 2], v_max, chunk=chunk)

    monkeypatch.setattr(backends, name, {"unchanged": unchanged, "half": half_batch}[how])


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("how", ["unchanged", "half"])
def test_broken_update_is_not_correct(monkeypatch, name, how):
    cell = tiny_cell(name, **CELLS[name])
    _break_update(monkeypatch, cell["config_data"]["cluster"]["backend"], how)
    rec = _run(cell)
    assert not rec["correct"] and rec["failed"] == rec["attempted"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_altered_answer_is_not_correct(monkeypatch, name):
    from repro.cluster import api

    orig = api.canonical_labels

    def altered(c):
        out = np.array(orig(c))
        out[len(out) // 2] = out.max() + 1  # one node moved to a new community
        return out

    monkeypatch.setattr(api, "canonical_labels", altered)
    rec = _run(tiny_cell(name, **CELLS[name]))
    assert not rec["correct"]
    assert rec["checks"]["label_mismatches"]["value"] == 1


def test_dropped_rows_in_the_source_are_not_correct(monkeypatch):
    """A source read that loses rows shows in ``edges_seen`` and labels."""
    orig = stream.BinaryFileSource.iter_slices

    def lossy(self, start=0):
        for sl in orig(self, start):
            yield sl[1:]

    monkeypatch.setattr(stream.BinaryFileSource, "iter_slices", lossy)
    rec = _run(tiny_cell("g500-chunked-bin", **CELLS["g500-chunked-bin"]))
    assert not rec["correct"]
    assert rec["checks"]["edges_seen_gap"]["value"] > 0


def test_window_ends_at_a_batch_boundary(monkeypatch):
    """The pass running at the deadline stops at the next batch boundary,
    and that cut pass is compared too.  The file is read slowly, in slices
    that do not line up with the batches, so the deadline falls inside a
    pass's read."""
    orig = stream.BinaryFileSource.iter_slices

    def slow(self, start=0):
        for sl in orig(self, start):
            for k in range(0, sl.shape[0], 1000):
                time.sleep(0.001)
                yield sl[k : k + 1000]

    monkeypatch.setattr(stream.BinaryFileSource, "iter_slices", slow)
    cell = tiny_cell("g500-chunked-bin", scale=14, edgefactor=32, batch=2048)
    rec = _run(cell, seconds=0.05)
    last = rec["passes"][-1]
    m = cell["config_data"]["m"]
    assert last["cut"] and last["rows"] % 2048 == 0 and 0 < last["rows"] < m
    assert rec["correct"]
