"""A server child with a fault planted where a grouped Sum is produced: of
the GroupBy answers the backend's `_group_sum` returns, every third that
holds a group has its first group's sum one too large (the counts are left
as they are). Used by test_ssb.py; the benchmark's own runs never start
it."""

import itertools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import launcher  # noqa: E402
from pilosa_tpu.exec import tpu  # noqa: E402

_answers = itertools.count(1)
_group_sum = tpu.TPUBackend._group_sum


def _altered(self, *args, **kwargs):
    out = _group_sum(self, *args, **kwargs)
    if out is not None and len(out) and next(_answers) % 3 == 0:
        out.sums = out.sums.copy()
        out.sums[0] += 1
    return out


tpu.TPUBackend._group_sum = _altered

if __name__ == "__main__":
    sys.exit(launcher.main())
