"""The persistent compilation cache lands at a fixed directory."""

import re

import jax
import pytest

from repro.utils import compile_cache


KEYS = ("jax_compilation_cache_dir", "jax_compilation_cache_include_metadata_in_key",
        "jax_hlo_source_file_canonicalization_regex")


@pytest.fixture
def restore_cache_dir():
    before = {k: getattr(jax.config, k) for k in KEYS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_env_var_directory_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_directory_is_fixed_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    checkout = compile_cache.Path(__file__).resolve().parents[1]
    assert first == second == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_the_key_holds_op_metadata_but_not_the_checkouts_place(monkeypatch, restore_cache_dir):
    """Two modules that differ in an op's scope alone get two keys; the
    same module lowered from two checkouts gets one."""
    import hashlib

    import jax.numpy as jnp
    from jax._src import cache_key

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    compile_cache.enable_compile_cache()

    def key(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0

        ir = jax.jit(f).lower(jnp.ones(4)).compiler_ir()
        return hashlib.sha256(cache_key._serialize_ir(ir, cache_key.IgnoreCallbacks.NO)).hexdigest()

    assert key("obs.optimizer") != key("obs.apply")
    here = str(compile_cache.CHECKOUT / "src" / "repro" / "x.py")
    assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex, "", here) == "src/repro/x.py"
