"""A server child with a fault planted where a Sum is produced: every
third sum the backend's `bsi_sum` returns is one too large (its count is
left as it is). Used by test_rehearsal.py; the benchmark's own runs never
start it."""

import itertools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import launcher  # noqa: E402
from pilosa_tpu.exec import tpu  # noqa: E402

_calls = itertools.count(1)
_bsi_sum = tpu.TPUBackend.bsi_sum


def bsi_sum(self, index, field_name, shards, filter_call=None):
    out = _bsi_sum(self, index, field_name, shards, filter_call)
    if out is not None and next(_calls) % 3 == 0:
        out = (out[0] + 1, out[1])
    return out


tpu.TPUBackend.bsi_sum = bsi_sum

if __name__ == "__main__":
    sys.exit(launcher.main())
