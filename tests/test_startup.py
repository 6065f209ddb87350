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


class TestCompileCachePlacement:
    def test_env_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                 config_updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert configure_compile_cache() == "/x"
        assert config_updates == []

    def test_empty_env_means_no_cache(self, monkeypatch, config_updates):
        """An empty value is jax's own 'no persistent cache'
        (scripts/crash_matrix.py children, which are SIGKILLed mid-write)."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
        assert configure_compile_cache() == ""
        assert config_updates == []

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch,
                                                   config_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
        assert configure_compile_cache() == want
        assert config_updates == [("jax_compilation_cache_dir", want)]


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

    def test_bench_without_a_tpu(self):
        proc = _run([sys.executable, "bench.py"], _REPO, JAX_PLATFORMS="cpu")
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
