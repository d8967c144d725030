"""chip_smoke.py off the chip: what it must refuse, and how it fails.
The legs themselves only run where there is a TPU."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (jax-free at import)
from horovod_tpu.common import device  # noqa: E402

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "jax": "x",
       "jaxlib": "x", "libtpu": "x"}


def test_refuses_without_a_tpu_before_compiling_anything():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "JAX found platform 'cpu', not 'tpu'" in proc.stdout
    assert "nothing was compiled" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_a_failing_leg_fails_the_run(monkeypatch, capsys):
    # A leg name the child does not know exits non-zero before it
    # imports anything.
    monkeypatch.setattr(chip_smoke, "probe", lambda: dict(TPU))
    monkeypatch.setattr(chip_smoke, "ONE_CHIP_LEGS", ("nope",))
    monkeypatch.setitem(chip_smoke.LEG_TIMEOUT_S, "nope", 60)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "FAILED: leg nope@1 (exit code 1" in out
    assert '"ok"' not in out


def test_a_leg_past_its_time_limit_is_killed_with_all_it_started():
    # The child parks a grandchild in a session of its own, as the
    # launcher's workers are; neither may outlive run_child.
    code = ("import subprocess, sys, time; "
            "subprocess.Popen([sys.executable, '-c', "
            "'import time; time.sleep(120)'], start_new_session=True); "
            "print('up', flush=True); time.sleep(120)")
    rc, lines = chip_smoke.run_child("sleeper", [sys.executable, "-c", code],
                                     timeout_s=1.5)
    assert rc == "timeout" and lines == ["up\n"]
    assert chip_smoke.tagged("%d.sleeper" % os.getpid()) == []


def test_compile_cache_follows_the_environment_or_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert device.place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before  # not ours
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert device.place_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # Either way an entry is keyed with its operations' names: a
        # step is never handed an executable from before its scopes.
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_one_decision_for_compiled_or_interpreted_kernels(monkeypatch):
    for platform, want in (("tpu", True), ("cpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda p=platform: p)
        assert device.on_tpu() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        device.on_tpu()
