"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``peaks.json``, with its source).  A chip not in the table is an error."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = json.loads((Path(__file__).parent / "peaks.json").read_text())


def lookup(device_kind: str) -> dict:
    try:
        return TABLE["devices"][device_kind]
    except KeyError:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json") from None
