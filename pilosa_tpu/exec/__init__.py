"""Query execution engine.

The executor evaluates a parsed PQL query against the holder: per-shard
bitmap-call evaluation fans out over a mapper (serial/threaded locally,
cluster-wide over RPC, or batched on TPU via the device backend in
pilosa_tpu/ops), with streaming reduction of partial results — the
structure of the reference's mapReduce (reference executor.go:2460).
"""

from pilosa_tpu.exec.executor import Executor, ExecOptions
from pilosa_tpu.exec.result import (
    GroupCount,
    GroupCounts,
    FieldRow,
    PairsField,
    RowIDs,
    SignedRow,
    ValCount,
)
