"""nerrf_tpu.utils: where the two compile caches go."""

import jax

from nerrf_tpu import utils
from nerrf_tpu.compilecache import default_cache_dir


def test_cache_dir_set_from_outside_holds_both_caches(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, code sets no
    directory, and the AOT cache lives under the same root."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("NERRF_NO_COMPILE_CACHE", raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    utils.enable_compilation_cache()
    assert updates == []
    assert utils.compile_cache_dir() == str(tmp_path)
    assert default_cache_dir() == str(tmp_path / "aot")


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(
        monkeypatch, repo_root):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("NERRF_NO_COMPILE_CACHE", raising=False)
    updates = []
    # record, don't arm: the on-disk cache must stay out of the CPU suite
    # (tests/conftest.py)
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    utils.enable_compilation_cache()
    fixed = str(repo_root / ".compile_cache")
    assert updates == [("jax_compilation_cache_dir", fixed)]
    assert default_cache_dir() == str(repo_root / ".compile_cache" / "aot")
    ignored = (repo_root / ".gitignore").read_text().split()
    assert ".compile_cache/" in ignored
