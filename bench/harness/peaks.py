"""The chip's peaks, from `bench/peaks.json`, keyed by ``device_kind``.  A
device with no entry is an error, not a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def for_device(run):
    with open(_PATH) as f:
        t = json.load(f)["devices"]
    kind = run.data["device_kind"]
    if kind not in t:
        raise KeyError(f"no peaks for device kind {kind!r} (known: "
                       f"{sorted(t)})")
    return t[kind]
