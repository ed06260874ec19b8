"""The compile-cache rule (utils/jax_cache.py): a cache placed from outside
wins, otherwise ONE fixed git-ignored directory inside the checkout — a
cache directory that moves between runs never hits."""
import os

import jax
import pytest

from transmogrifai_tpu.utils import jax_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_rule(monkeypatch):
    """Let ``ensure_compilation_cache`` run again, and put the session's
    cache directory back afterwards (no program compiles in between)."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax_cache, "_done", False)
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_placed_from_outside_is_left_alone(fresh_rule, tmp_path):
    # what jax itself does on reading JAX_COMPILATION_CACHE_DIR
    placed = str(tmp_path / "placed_cache")
    jax.config.update("jax_compilation_cache_dir", placed)
    jax_cache.ensure_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == placed
    assert not os.path.exists(placed)    # nothing created there by the rule


def test_default_cache_is_the_fixed_in_checkout_directory(fresh_rule):
    jax.config.update("jax_compilation_cache_dir", None)
    jax_cache.ensure_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == jax_cache.CACHE_DIR
    assert jax_cache.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_no_other_cache_path_is_set_in_code_or_tests():
    hits = []
    for root in ("transmogrifai_tpu", "tests", "chip_smoke.py",
                 "__graft_entry__.py"):
        path = os.path.join(REPO, root)
        files = ([path] if os.path.isfile(path) else
                 [os.path.join(d, f) for d, _, fs in os.walk(path)
                  for f in fs if f.endswith(".py")])
        for f in files:
            if f.endswith(("utils/jax_cache.py", "test_compile_cache.py")):
                continue
            with open(f, encoding="utf-8") as fh:
                if "jax_compilation_cache_dir\"," in fh.read():
                    hits.append(os.path.relpath(f, REPO))
    assert not hits, f"compile-cache directory set outside the rule: {hits}"
