"""Where the entry points put JAX's persistent compilation cache.

``jax.config.update`` is replaced by a recorder, so no test here turns
the cache on.
"""
import re
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as CC

CHECKOUT = Path(__file__).resolve().parents[1]
CANON = ("jax_hlo_source_file_canonicalization_regex", CC.SOURCE_PREFIX)


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, updates):
    """With the variable set, no cache directory is set in code (only
    the source-path cut, which places no cache)."""
    monkeypatch.setenv(CC.ENV_VAR, "/elsewhere/cache")
    assert CC.enable_compile_cache() == "/elsewhere/cache"
    assert updates == [CANON]


def test_default_dir_is_fixed_and_gitignored(monkeypatch, updates):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert CC.enable_compile_cache() == want
    assert CC.enable_compile_cache() == want          # same path every run
    assert updates == [CANON, ("jax_compilation_cache_dir", want)] * 2
    ignored = (CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_source_locations_drop_the_checkout_path():
    """Kernel source locations, which the cache key hashes, come out the
    same wherever the checkout lives; paths outside it are kept."""
    kernel = CHECKOUT / "src" / "repro" / "kernels" / "k.py"
    assert re.sub(CC.SOURCE_PREFIX, "", str(kernel)) == \
        "src/repro/kernels/k.py"
    other = "/usr/lib/python3/site-packages/jax/x.py"
    assert re.sub(CC.SOURCE_PREFIX, "", other) == other
