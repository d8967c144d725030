"""Launcher utilities: host parsing, secrets, codecs, timeouts.

Reference parity: ``horovod/runner/common/util/{hosts.py, secret.py,
codec.py, timeout.py, host_hash.py}``.
"""

from __future__ import annotations

import base64
import dataclasses
import os
import pickle
import secrets as _secrets
import socket
import time
from typing import List


@dataclasses.dataclass(frozen=True)
class HostInfo:
    hostname: str
    slots: int


def parse_hosts(hosts: str) -> List[HostInfo]:
    """Parse "host1:4,host2:2" (slots default 1)."""
    out = []
    for part in hosts.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, slots = part.rsplit(":", 1)
            out.append(HostInfo(name, int(slots)))
        else:
            out.append(HostInfo(part, 1))
    if not out:
        raise ValueError("no hosts parsed from %r" % hosts)
    return out


def parse_hostfile(path: str) -> List[HostInfo]:
    """Hostfile lines: "<host> slots=<n>" (mpirun style) or "host:n"."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.split("#")[0].strip()
            if not line:
                continue
            if "slots=" in line:
                name, _, rest = line.partition(" ")
                slots = int(rest.split("slots=")[1].split()[0])
                out.append(HostInfo(name.strip(), slots))
            else:
                out.extend(parse_hosts(line))
    if not out:
        raise ValueError("no hosts in hostfile %s" % path)
    return out


def total_slots(hosts: List[HostInfo]) -> int:
    return sum(h.slots for h in hosts)


def make_secret() -> str:
    """Shared HMAC secret distributed to workers (reference secret.py)."""
    return _secrets.token_hex(16)


def dumps_base64(obj) -> str:
    """Pickle+base64 codec for env-safe payloads (reference codec.py)."""
    return base64.b64encode(pickle.dumps(obj)).decode()


def loads_base64(s: str):
    return pickle.loads(base64.b64decode(s.encode()))


def host_hash() -> str:
    """Stable identifier for this host, used to group local ranks
    (reference host_hash.py)."""
    return socket.gethostname()


class Timeout:
    """Deadline helper with contextual error messages (reference
    timeout.py)."""

    def __init__(self, seconds: float, message: str = "operation"):
        self._deadline = time.monotonic() + seconds
        self._message = message

    def remaining(self) -> float:
        return max(0.0, self._deadline - time.monotonic())

    def expired(self) -> bool:
        return time.monotonic() >= self._deadline

    def check(self):
        if self.expired():
            raise TimeoutError("%s timed out" % self._message)


def lsf_available() -> bool:
    """True under an LSF allocation (reference ``util/lsf.py``)."""
    return "LSB_MCPU_HOSTS" in os.environ or "LSB_HOSTS" in os.environ


def parse_lsf_hosts() -> List[HostInfo]:
    """Hosts/slots from the LSF environment (reference ``lsf.py``):
    ``LSB_MCPU_HOSTS`` = "host1 4 host2 4"; ``LSB_HOSTS`` = one token
    per slot."""
    mcpu = os.environ.get("LSB_MCPU_HOSTS")
    if mcpu:
        toks = mcpu.split()
        if len(toks) % 2:
            raise ValueError("malformed LSB_MCPU_HOSTS: %r" % mcpu)
        return [HostInfo(toks[i], int(toks[i + 1]))
                for i in range(0, len(toks), 2)]
    hosts = os.environ.get("LSB_HOSTS", "").split()
    if not hosts:
        raise ValueError("no LSF host environment found")
    # one token per slot, possibly interleaved: count ALL occurrences
    # per host, first-seen order (adjacent-only runs would split a
    # host into duplicate entries and collide local_ranks)
    counts: dict = {}
    for h in hosts:
        counts[h] = counts.get(h, 0) + 1
    return [HostInfo(h, c) for h, c in counts.items()]


def slurm_available() -> bool:
    """True under a Slurm allocation."""
    return "SLURM_JOB_NODELIST" in os.environ or \
        "SLURM_NODELIST" in os.environ


def _expand_slurm_nodelist(nodelist: str) -> List[str]:
    """Expand "node[1-3,7],gpu01" into explicit hostnames (the subset
    of Slurm's syntax schedulers actually emit: comma lists and one
    [a-b,c] range block per name, zero-padded)."""
    hosts: List[str] = []
    i, n = 0, len(nodelist)
    while i < n:
        j = i
        while j < n and nodelist[j] not in ",[":
            j += 1
        prefix = nodelist[i:j]
        if j < n and nodelist[j] == "[":
            k = nodelist.index("]", j)
            for part in nodelist[j + 1:k].split(","):
                if "-" in part:
                    lo, hi = part.split("-")
                    width = len(lo)
                    for v in range(int(lo), int(hi) + 1):
                        hosts.append(prefix + str(v).zfill(width))
                else:
                    hosts.append(prefix + part)
            i = k + 2  # skip "]," if present
        else:
            if prefix:
                hosts.append(prefix)
            i = j + 1
    return hosts


def _expand_slurm_tasks(spec: str, num_hosts: int) -> List[int]:
    """Expand SLURM_TASKS_PER_NODE "4(x2),2" into per-host counts."""
    counts: List[int] = []
    for part in spec.split(","):
        if "(x" in part:
            base, times = part.split("(x")
            counts.extend([int(base)] * int(times.rstrip(")")))
        else:
            counts.append(int(part))
    if len(counts) < num_hosts:  # pad with last
        counts.extend([counts[-1]] * (num_hosts - len(counts)))
    return counts[:num_hosts]


def parse_slurm_hosts() -> List[HostInfo]:
    """Hosts/slots from the Slurm environment."""
    nodelist = os.environ.get("SLURM_JOB_NODELIST") or \
        os.environ.get("SLURM_NODELIST")
    if not nodelist:
        raise ValueError("no Slurm host environment found")
    hosts = _expand_slurm_nodelist(nodelist)
    tasks = os.environ.get("SLURM_TASKS_PER_NODE") or \
        os.environ.get("SLURM_NTASKS_PER_NODE") or "1"
    counts = _expand_slurm_tasks(tasks, len(hosts))
    return [HostInfo(h, c) for h, c in zip(hosts, counts)]


def scheduler_hosts() -> List[HostInfo]:
    """Hosts from a detected batch scheduler (LSF, then Slurm), or an
    empty list when not running under one — the launcher's fallback
    when no -H/--hostfile is given (reference: lsf/slurm detection in
    ``horovod/runner/launch.py``).  A malformed scheduler environment
    is reported loudly (then the next source is tried) rather than
    silently degrading to single-host."""
    import sys
    if lsf_available():
        try:
            return parse_lsf_hosts()
        except ValueError as exc:
            print("[launcher] WARNING: LSF detected but unusable: %s"
                  % exc, file=sys.stderr)
    if slurm_available():
        try:
            return parse_slurm_hosts()
        except ValueError as exc:
            print("[launcher] WARNING: Slurm detected but unusable: %s"
                  % exc, file=sys.stderr)
    return []


def routable_ip() -> str:
    """This host's address as peers would route to it (reference:
    driver-service NIC discovery): the source address of an outbound
    UDP connect, falling back to hostname resolution."""
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 80))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        try:
            return socket.gethostbyname(socket.gethostname())
        except socket.gaierror:
            return "127.0.0.1"


def _bindable(host: str, port: int) -> bool:
    s = socket.socket()
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, port))
        return True
    except OSError:
        return False
    finally:
        s.close()


def find_free_port_base(size: int, host: str = "127.0.0.1") -> int:
    """A port base for a world of ``size`` ranks: the tcp core binds
    ``[base, base + size)`` and the launchers derive the jax coordinator's
    port (``base + size + 101``) and libtpu's (``base + size + 201 + i``)
    from it.  Seconds pass between this call and a worker's bind, so the
    base is drawn from under the range the kernel itself draws from for
    outgoing connections and binds to port 0: a number from that range
    (bind 0, close, hand it on) can go to any connection of any process
    on the box meanwhile, and of the block only the first port had ever
    been looked at — with five worlds forming at once one formation in
    twenty lost a rank to it ("native core init failed")."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            floor = int(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        floor = 32768
    top = floor - size - 400          # the derived ports stay under it too
    for _ in range(256):
        if top - 6000 >= 1024:
            base = top - 6000 + _secrets.randbelow(6000)
        else:                         # no room under the kernel's range
            s = socket.socket()
            s.bind((host, 0))
            base = s.getsockname()[1]
            s.close()
        if all(_bindable(host, base + i) for i in range(size)):
            return base
    raise RuntimeError("no block of %d free ports found on %s"
                       % (size, host))
