"""Process start-up: where the compile cache goes, and what happens without
a TPU. Cheap and CPU-only. (That a kernel self-check failure on a TPU is an
error, not a fallback: tests/test_ops.py TestSketchKernelSelfCheck.)"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from commefficient_tpu.utils import configure_compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


# the one thing set wherever the cache lives: its key includes the programs'
# metadata, where the stage names are (tests/test_tracing.py)
_KEYED = ("jax_compilation_cache_include_metadata_in_key", True)


class TestCompileCachePlacement:
    def test_env_wins_and_no_other_place_is_set_in_code(self, monkeypatch,
                                                        config_updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert configure_compile_cache() == "/x"
        assert config_updates == [_KEYED]

    def test_empty_env_means_no_cache(self, monkeypatch, config_updates):
        """An empty value is jax's own 'no persistent cache'
        (scripts/crash_matrix.py children, which are SIGKILLed mid-write)."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
        assert configure_compile_cache() == ""
        assert config_updates == [_KEYED]

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch,
                                                   config_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
        assert configure_compile_cache() == want
        assert config_updates == [_KEYED,
                                  ("jax_compilation_cache_dir", want)]

    def test_a_scope_name_changes_the_cache_key(self):
        """What the setting is for: two programs that differ only in a
        ``jax.named_scope`` must not share a cache entry, or a capture
        shows the names of whichever was compiled first."""
        import hashlib

        import jax.numpy as jnp
        from jax._src import cache_key, config as jax_config

        def digest(scope, include):
            def f(x):
                with jax.named_scope(scope):
                    return x + 1.0

            module = jax.jit(f).lower(jnp.zeros(4)).compiler_ir()
            h = hashlib.sha256()
            with jax_config.compilation_cache_include_metadata_in_key(
                    include):
                cache_key._hash_computation(h, module,
                                            cache_key.IgnoreCallbacks.NO)
            return h.hexdigest()

        assert digest("fed_a", False) == digest("fed_b", False)
        assert digest("fed_a", True) != digest("fed_b", True)


def _run(cmd, cwd, **env):
    return subprocess.run(
        cmd, cwd=cwd, env={**os.environ, **env}, capture_output=True,
        text=True, timeout=120)


def _json_lines(text):
    return [line for line in text.splitlines() if line.startswith("{")]


class TestNoChipNoNumber:
    def test_result_line_has_the_contract_keys_and_no_others(self):
        """The driver refuses any other last line (extra keys included)."""
        sys.path.insert(0, _REPO)
        try:
            import chip_smoke  # the parent half never imports jax
        finally:
            sys.path.remove(_REPO)
        line = chip_smoke.result_line(True, {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
        assert "\n" not in line
        assert json.loads(line) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

    def test_chip_smoke_without_a_tpu(self):
        proc = _run([sys.executable, "chip_smoke.py"], _REPO,
                    JAX_PLATFORMS="cpu")
        assert proc.returncode != 0
        assert _json_lines(proc.stdout) == []
        assert "not a TPU" in proc.stdout + proc.stderr

    def test_chip_smoke_alone_in_a_directory(self, tmp_path):
        shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
        proc = _run([sys.executable, "chip_smoke.py"], tmp_path)
        assert proc.returncode != 0
        assert _json_lines(proc.stdout) == []

    def test_benchmark_without_a_tpu(self):
        """The yardstick gives no CPU number: a cell run without its chip
        (and without --rehearse) ends non-zero and prints no result, inside
        _run's own 120 s limit."""
        proc = _run([sys.executable, "benchmark/run.py", "--workload",
                     "resnet9_sketch_1c", "--seed", "1", "--seconds", "1"],
                    _REPO, JAX_PLATFORMS="cpu")
        assert proc.returncode != 0
        assert _json_lines(proc.stdout) == []
        assert "needs 1 TPU chip" in proc.stdout + proc.stderr
