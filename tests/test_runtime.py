"""tpu_dist.runtime: the two process-level rules every entry point shares,
and the no-fallback contracts PR 21 put on the device path (an unlisted TPU
has no peak; a step program the chip's compiler refuses raises)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import runtime


def test_pallas_interpret_one_rule(monkeypatch):
    assert runtime.pallas_interpret() is True          # the CPU test mesh
    assert runtime.pallas_interpret(False) is False    # explicit wins
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.pallas_interpret() is False
    assert runtime.pallas_interpret(True) is True


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_include_metadata_in_key")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_compile_cache_placed_from_outside_or_inside_the_checkout(
        monkeypatch, cache_config, tmp_path):
    # set from outside: JAX reads the variable itself, the code sets nothing
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
    # unset: one fixed directory inside the checkout, the same every call
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert runtime.enable_compile_cache() == runtime.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == runtime.DEFAULT_CACHE_DIR
    assert runtime.DEFAULT_CACHE_DIR.endswith("/.jax_cache")
    assert runtime.enable_compile_cache() == runtime.DEFAULT_CACHE_DIR


def test_a_cached_executable_is_keyed_on_its_names_too(cache_config):
    """Two programs that differ only in a ``jax.named_scope`` (metadata)
    must not share a cache entry: the one read back would carry the other's
    ``op_name``s, which the trace readers join on (PR 24)."""
    import jax.numpy as jnp
    from jax._src import cache_key, compiler

    runtime.enable_compile_cache()

    def key(scope):
        def f(x):
            with jax.named_scope(scope):
                return x * 2.0
        lowered = jax.jit(f).lower(jnp.ones((4,)))
        dev = jax.devices()[:1]
        return cache_key.get(
            lowered.compiler_ir("stablehlo"), np.array(dev),
            compiler.get_compile_options(1, 1), dev[0].client)

    assert key("paged_read") != key("unnamed")
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    assert key("paged_read") == key("unnamed")      # JAX's default


@pytest.mark.parametrize("kind,expect", [
    ("TPU v5 lite", (197.0, 819.0, False)),   # the chip the builders reach
    ("cpu", (1.0, 1.0, True)),                # nominal, flagged
    ("TPU v9 hyper", None),                   # a TPU nobody listed: error
])
def test_peaks_unknown_tpu_kind_is_an_error(monkeypatch, kind, expect):
    from tpu_dist.obs import effective_peak_tflops
    from tpu_dist.obs.attr import effective_peak_gbps
    from tpu_dist.plan.tune import device_peaks

    dev = types.SimpleNamespace(device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    if expect is None:
        for fn in (effective_peak_tflops, effective_peak_gbps,
                   lambda: device_peaks(kind)):
            with pytest.raises(ValueError, match="no published"):
                fn()
        return
    tf, gb, nominal = expect
    assert effective_peak_tflops() == (tf, nominal)
    assert effective_peak_gbps() == (gb, nominal)
    assert device_peaks(kind) == {"tflops": tf, "gbps": gb,
                                  "nominal": nominal}


def test_program_stats_raises_on_tpu_and_degrades_elsewhere(monkeypatch):
    from tpu_dist.utils.telemetry import program_stats

    not_jitted = lambda x: x          # .lower() fails: the probe's bad day
    assert program_stats(not_jitted, jnp.ones(())) == {"hbm_bytes": None,
                                                       "flops": None}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(AttributeError):
        program_stats(not_jitted, jnp.ones(()))
