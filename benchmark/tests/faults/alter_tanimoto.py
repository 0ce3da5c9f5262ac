"""A server child with a fault planted where a Tanimoto TopN is produced:
of the answers the backend's `topn_tanimoto_async` resolves, every third
that holds a row has its first count one too large. Used by
test_tanimoto.py; the benchmark's own runs never start it."""

import itertools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import launcher  # noqa: E402
from pilosa_tpu.exec import tpu  # noqa: E402

_answers = itertools.count(1)
_async = tpu.TPUBackend.topn_tanimoto_async


def topn_tanimoto_async(self, index, field_name, shards, legs):
    resolver = _async(self, index, field_name, shards, legs)
    if resolver is None:
        return None

    def resolve(deliver=None):
        """Everything read back first, then altered, then handed over at
        once: the fault is in the counts, not in when they arrive."""
        out = []
        for rows, counts in resolver():
            if counts.size and next(_answers) % 3 == 0:
                counts = counts.copy()
                counts[0] += 1
            out.append((rows, counts))
        if deliver is not None:
            deliver(range(len(out)), out)
        return out

    return resolve


tpu.TPUBackend.topn_tanimoto_async = topn_tanimoto_async

if __name__ == "__main__":
    sys.exit(launcher.main())
