"""A server child whose chips do not exchange their partial counts: the
backend's psum is the identity, so each answer is one chip's part. Used by
test_rehearsal.py on a mesh cell; the benchmark's own runs never start it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import launcher  # noqa: E402
from pilosa_tpu.exec import tpu  # noqa: E402

tpu.TPUBackend._psum = lambda self, x: x

if __name__ == "__main__":
    sys.exit(launcher.main())
