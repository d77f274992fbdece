"""The one table of device peaks, keyed by `device_kind` as JAX reports it.
A device that is not in peaks.json is an error, not a default."""
import json
import os


def peaks_for(device_kind):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}: "
                       "add a row with its source, do not guess")
    return table[device_kind]
