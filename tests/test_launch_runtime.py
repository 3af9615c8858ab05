"""Entry-point start-up: the platform check and the compile cache, plus a
CPU rehearsal of ``chip_smoke.py``'s served and flash phases at the tiny
presets (its kernel phase runs at serving shapes, which interpret mode
makes minutes long; tests/test_kernels.py covers the kernels)."""
import importlib.util
import pathlib
import sys

import jax
import pytest

from repro.launch import runtime, serve, watch

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("entry,platforms", [
    ("serve", None), ("watch", None), ("chip_smoke", None),
    ("chip_smoke", "cpu")])
def test_entry_point_refuses_non_tpu(monkeypatch, chip_smoke, entry,
                                     platforms):
    """Off a TPU each entry point exits before building anything; only
    serve/watch admit an explicit JAX_PLATFORMS=cpu."""
    main = {"serve": serve.main, "watch": watch.main,
            "chip_smoke": chip_smoke.main}[entry]
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(sys, "argv", [entry])
    with pytest.raises(SystemExit, match="found no TPU"):
        main()


def test_cpu_on_purpose_is_admitted(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.require_tpu("serve").platform == "cpu"


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins untouched; else one fixed,
    git-ignored directory in the checkout."""
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = runtime.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if from_env:
        assert after == before  # JAX reads the variable itself
    else:
        assert got == after == str(ROOT / ".jax_cache")
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_served_and_flash_phases_rehearse_on_cpu(chip_smoke):
    engine, tok, ds = chip_smoke.served_phase(smoke=True, n=128)
    assert engine.stats["prefill_tokens"] > 0
    chip_smoke.flash_phase(engine, tok, ds)
