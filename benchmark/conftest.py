"""For every test under benchmark/: the tall field of chem-1chip
(draws/morgan_families.py, 1.7 M rows) is rehearsed at a height a CPU
walks in seconds. A rehearsal cuts a configuration in `shards` alone and
this one has one shard, so the draw reads the height from the environment
where it is set, and the runs the tests start inherit it; no run of a cell
sets it. The height does not enter the data's key (harness/dataset.py
`data_key`): every run the tests start has a `--data-root` of its own, and
so must one made by hand. A `benchmark` PR owes the rehearsal a `--rows`
beside `--shards` that enters the key (PERF.md, Open questions); this file
and the draw's read of the environment go when it lands."""

import os

os.environ.setdefault("BENCH_REHEARSAL_ROWS", "24000")
