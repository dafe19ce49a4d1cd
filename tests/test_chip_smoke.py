"""``chip_smoke.py`` rehearsed on the CPU: every phase's own checks at tiny
sizes (Pallas interpreted), its refusal to run without a TPU, and the
placement of the persistent compile cache."""

import importlib.util
import pathlib
import sys

import jax

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]

TINY = dict(
    n_paper=3000,
    m_paper=30_000,
    prefix=4096,
    paper_batch=1024,
    paper_k=2,
    n_exact=2048,
    m_exact=6000,
    exact_batch=1024,
    exact_k=2,
    n_small=256,
    m_small=3000,
    small_batch=256,
    tenants=4,
)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module  # dataclasses resolve the module
    spec.loader.exec_module(module)
    return module


def test_chip_smoke_phases_pass_at_tiny_sizes(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cs = _chip_smoke()
    out = cs.run(cs.Sizes(**TINY), seed=3)
    assert out["a"]["edges"] == TINY["m_paper"]
    assert out["a"]["communities"] > 1
    # off a TPU nothing is refused: both paths ran and matched their
    # references inside the phase
    assert out["c"] == {"fleet": "ok", "wavefront": "ok", "device_decode": "ok"}


def test_chip_smoke_fails_before_any_phase_without_tpu(capsys):
    cs = _chip_smoke()
    assert cs.main() == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "(a)" not in out


def test_compile_cache_respects_environment(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        placed = compile_cache.use_compile_cache()
        assert placed == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
