"""The LM cell's `explicit_share`: the reading of
`bench/metrics/explicit_share.leave.py`, from the same programs and spans,
which the LM cell's replays run too.  Moves ``rows_per_s``."""

import os

from bench.harness.core import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "explicit_share.leave.py")).read
