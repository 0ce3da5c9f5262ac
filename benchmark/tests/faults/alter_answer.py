"""A server child with a fault planted where an answer is produced: every
seventh batch of Counts gets one added to its first count. Used by
test_rehearsal.py; the benchmark's own runs never start it."""

import itertools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import launcher  # noqa: E402
from pilosa_tpu.exec import batcher  # noqa: E402

_calls = itertools.count(1)
_count = batcher.ShardLegBatcher.count


def count(self, index, calls, shards):
    out = _count(self, index, calls, shards)
    if next(_calls) % 7 == 0:
        out = [out[0] + 1] + list(out[1:])
    return out


batcher.ShardLegBatcher.count = count

if __name__ == "__main__":
    sys.exit(launcher.main())
