"""Children that are dead when their parent says so.

Copied from ``chip_smoke.py``: every process a run starts carries a tag
in its environment and is killed by that tag, whatever session it moved
to.  A process left alive would hold a chip.  No jax here.
"""

import os
import signal
import subprocess
import threading

TAG = "YARDSTICK_RUN"


def tagged(tag_value):
    """PIDs of the processes carrying this tag, ourselves excepted."""
    needle = ("%s=%s" % (TAG, tag_value)).encode()
    pids = []
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open("/proc/%s/environ" % name, "rb") as f:
                if needle in f.read().split(b"\0"):
                    pids.append(int(name))
        except OSError:
            pass  # gone already, or not ours to read
    return pids


def reap(tag_value):
    for pid in tagged(tag_value):
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass


def run_tagged(argv, cwd, env, tag_value, timeout_s, log_path):
    """Run ``argv`` to its end or its time limit with its output sent to
    ``log_path``; returns the exit code, or ``"timeout"``.  The child and
    everything it started are dead, and waited for, when this returns."""
    env = dict(env, **{TAG: tag_value, "PYTHONUNBUFFERED": "1"})
    timed_out = threading.Event()

    def expire():
        timed_out.set()
        reap(tag_value)

    with open(log_path, "wb") as log:
        proc = subprocess.Popen(argv, cwd=cwd, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
        timer = threading.Timer(timeout_s, expire)
        timer.start()
        try:
            rc = proc.wait()
        finally:
            timer.cancel()
            reap(tag_value)
            proc.wait()
    return "timeout" if timed_out.is_set() else rc
