"""Regression tests for the persistent-XLA-cache placement and its
validation guard (`benchmarks.run.enable_compilation_cache`,
`validate_cache_dir`).

The scar (PR 8): a results/.jax_cache serialized against an older
jaxlib/engine deserialized into poisoned executables that hung
armed-engine runs roughly 1-in-3. The guard keys the cache dir it
creates on (jaxlib version, ENGINE_SCHEMA) via a CACHE_KEY marker file
and clears its own dir when the key changes. It never deletes entries
in a directory it did not create (no marker), and a directory placed
from outside through ``JAX_COMPILATION_CACHE_DIR`` is used as is and
never touched. Without the variable the cache path is anchored on the
repo root, so it is the same from any cwd."""
from __future__ import annotations

import os

from benchmarks.run import (CACHE_DIR, CACHE_KEY_FILE, cache_key,
                            enable_compilation_cache, validate_cache_dir)


def _fill(d, names=("entry_a.bin", "entry_b.bin")):
    os.makedirs(d, exist_ok=True)
    for name in names:
        with open(os.path.join(d, name), "wb") as f:
            f.write(b"\x00serialized-executable\x00")
    return names


def _marker(d):
    return os.path.join(d, CACHE_KEY_FILE)


def test_cache_key_tracks_jaxlib_and_schema():
    import jaxlib

    from repro.engine import ENGINE_SCHEMA

    key = cache_key()
    assert jaxlib.__version__ in key
    assert f"engine_schema={ENGINE_SCHEMA}" in key


def test_fresh_dir_is_marked(tmp_path):
    d = str(tmp_path / "cache")
    assert validate_cache_dir(d, key="k1") == "fresh"
    with open(_marker(d)) as f:
        assert f.read().strip() == "k1"
    # empty-but-existing dir is fresh too
    d2 = str(tmp_path / "cache2")
    os.makedirs(d2)
    assert validate_cache_dir(d2, key="k1") == "fresh"


def test_matching_marker_preserves_entries(tmp_path):
    d = str(tmp_path / "cache")
    validate_cache_dir(d, key="k1")
    names = _fill(d)
    assert validate_cache_dir(d, key="k1") == "match"
    for name in names:
        assert os.path.exists(os.path.join(d, name))  # entries survive


def test_stale_marker_clears_dir(tmp_path):
    """The direct scar shape: entries written under an older key."""
    d = str(tmp_path / "cache")
    validate_cache_dir(d, key="jaxlib=0.4.0;engine_schema=9")
    names = _fill(d)
    assert validate_cache_dir(d, key=cache_key()) == "cleared"
    for name in names:
        assert not os.path.exists(os.path.join(d, name))  # poison gone
    with open(_marker(d)) as f:
        assert f.read().strip() == cache_key()  # re-marked for today
    # and now it matches
    assert validate_cache_dir(d, key=cache_key()) == "match"


def test_unmarked_nonempty_dir_clears(tmp_path):
    """A non-empty dir without a marker was not created by the guard:
    its provenance is unknown, so the guard refuses it ("foreign") —
    it neither clears nor marks it, and the caller must not use it."""
    d = str(tmp_path / "cache")
    names = _fill(d)
    assert validate_cache_dir(d, key="k1") == "foreign"
    for name in names:
        assert os.path.exists(os.path.join(d, name))
    assert not os.path.exists(_marker(d))


def test_enable_compilation_cache_validates(tmp_path, monkeypatch):
    """A cache dir placed from outside (JAX_COMPILATION_CACHE_DIR) is
    used as is: even a stale marker with entries is left untouched, and
    no directory is set in code."""
    d = str(tmp_path / "cache")
    validate_cache_dir(d, key="stale-key")
    _fill(d)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    import jax

    before = jax.config.jax_compilation_cache_dir
    assert enable_compilation_cache() == d
    assert os.path.exists(os.path.join(d, "entry_a.bin"))
    with open(_marker(d)) as f:
        assert f.read().strip() == "stale-key"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_anchored_on_repo_root():
    """Without the variable the cache path does not depend on the cwd:
    it is the git-ignored results/.jax_cache under the repo root."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CACHE_DIR == os.path.join(repo, "results", ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert "results/.jax_cache/" in f.read().split()


def test_enable_compilation_cache_skips_foreign_dir(tmp_path, monkeypatch):
    """Without the variable, a foreign dir at the cache path is neither
    cleared nor used: the cache stays off."""
    d = str(tmp_path / "cache")
    names = _fill(d)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    import jax

    before = jax.config.jax_compilation_cache_dir
    assert enable_compilation_cache(cache_dir=d) is None
    for name in names:
        assert os.path.exists(os.path.join(d, name))
    assert jax.config.jax_compilation_cache_dir == before
