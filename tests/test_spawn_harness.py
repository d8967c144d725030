"""The world-spawning harness (``tests/utils/spawn.py``): a world that
outlives its limit must fail with what its processes had written, the only
record of where it stood."""

import sys

import pytest

from tests.utils.spawn import run_world, spawn_world


def test_a_spawned_world_that_times_out_shows_every_ranks_output(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import os, time\n"
        "rank = os.environ['HOROVOD_RANK']\n"
        "print('rank', rank, 'reached the barrier', flush=True)\n"
        "if rank == '1':\n"
        "    time.sleep(600)\n")
    with pytest.raises(AssertionError) as failure:
        spawn_world(str(worker), 2, timeout=2, retry=False)
    said = str(failure.value)
    assert "timed out after 2 s" in said
    assert "rank 0 reached the barrier" in said
    assert "rank 1 reached the barrier" in said


def test_a_launcher_that_times_out_dies_with_its_children_and_shows_them():
    child = "import time; print('child up', flush=True); time.sleep(600)"
    launcher = ("import subprocess, sys; print('launcher up', flush=True); "
                "subprocess.run([sys.executable, '-c', %r])" % child)
    with pytest.raises(AssertionError) as failure:
        run_world([sys.executable, "-c", launcher], timeout=2)
    said = str(failure.value)
    assert "launcher up" in said and "child up" in said
    done = run_world([sys.executable, "-c", "print('fine')"], timeout=30)
    assert (done.returncode, done.stdout) == (0, "fine\n")
