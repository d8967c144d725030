"""The table of published peaks, keyed by ``device_kind``."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    """The table holds no peaks for this ``device_kind``."""


def peak_of(device_kind):
    """The published peaks of one chip of this kind.  A kind the table
    does not hold is an error: a utilisation against a guessed peak is
    worse than none."""
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice("no published peaks on record for device_kind %r; "
                       "add its data-sheet numbers and their source to %s"
                       % (device_kind, _PATH))
    return table[device_kind]
