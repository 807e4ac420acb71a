"""Backoff and flagenv tests (parity with reference timeutil/flagenv
tests)."""

import argparse

from doorman_tpu.utils.backoff import backoff
from doorman_tpu.utils.flagenv import flag_to_env, populate


def test_backoff_growth_and_clamp():
    assert backoff(1.0, 60.0, 0) == 1.0
    assert backoff(1.0, 60.0, 1) == 1.3
    assert abs(backoff(1.0, 60.0, 2) - 1.69) < 1e-9
    assert backoff(1.0, 60.0, 1000) == 60.0


def test_flag_to_env():
    assert flag_to_env("DOORMAN", "config") == "DOORMAN_CONFIG"
    assert flag_to_env("DOORMAN", "debug-port") == "DOORMAN_DEBUG_PORT"


def test_populate_from_env(monkeypatch):
    monkeypatch.setenv("DOORMAN_PORT", "1234")
    monkeypatch.setenv("DOORMAN_CONFIG", "file:/tmp/x.yml")
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--config", default="")
    parser.add_argument("--other", default="unchanged")
    populate(parser, "DOORMAN")
    args = parser.parse_args([])
    assert args.port == 1234
    assert args.config == "file:/tmp/x.yml"
    assert args.other == "unchanged"


def test_store_true_and_false_from_env(monkeypatch):
    monkeypatch.setenv("DOORMAN_VERBOSE", "true")
    monkeypatch.setenv("DOORMAN_NO_COLOR", "true")
    parser = argparse.ArgumentParser()
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("--no-color", action="store_false", dest="color")
    populate(parser, "DOORMAN")
    args = parser.parse_args([])
    assert args.verbose is True
    assert args.color is False  # env var applies the store_false flag


def test_command_line_beats_env(monkeypatch):
    monkeypatch.setenv("DOORMAN_PORT", "1234")
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    populate(parser, "DOORMAN")
    args = parser.parse_args(["--port", "7"])
    assert args.port == 7


def test_compile_cache_placement(monkeypatch):
    """An outside JAX_COMPILATION_CACHE_DIR is left to JAX; without it
    the cache goes to the fixed in-checkout path, never a temp name."""
    import jax

    from doorman_tpu.utils import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.place_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.place_compile_cache()
        assert path == str(compile_cache.REPO_CACHE)
        assert path.endswith("/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_native_library_is_keyed_on_source_hash():
    """The native store loads only a library built from this exact
    store.cc: a stale or foreign `.so` beside it has another name."""
    import hashlib

    from doorman_tpu import native

    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:12]
    assert native._LIB.name == f"_store.{digest}.so"
    assert native._LIB.parent == native._SRC.parent
