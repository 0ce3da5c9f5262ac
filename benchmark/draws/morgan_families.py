"""A library of molecules as the rows of a tall set field: row m holds the
bits of molecule m's folded fingerprint, every bit in the shard's first
`width_bits` columns (upstream Pilosa docs/examples.md "Chemical similarity
search": ChEMBL's molecules as rows, the 4,096 bits of a Morgan fingerprint
as columns, one shard). Shipped as positions: dense, 1.7 M rows would be
1.8 TB of bools.

Independent bits would give every search one answer, the query itself:
two unrelated fingerprints of ~58 bits share under one bit of 4,096. A real
library is made of families: series of analogues around a scaffold, a few
of them thousands strong and most of them small, so that a search at a
Tanimoto threshold of 70 to 90 returns from one molecule to a few thousand.
All of this is `assumed` (the configuration says so): the draw's keys are

  rows            molecules (the field's height)
  width_bits      columns a fingerprint folds to
  family_alpha    family sizes are drawn with P(size = s) ~ s ** -alpha ...
  family_max      ... over 1 .. family_max, until they hold every row; a
                  family's members lie anywhere among the rows
  core_bits       a family's scaffold: Poisson(core_bits) columns, uniform,
                  at least `core_min`
  core_min
  drop_mean       a member lacks each of its family's core bits with a
                  probability of its own, exponential with this mean (cut
                  at 0.5): substituents change some of the scaffold's bits
  own_bits        and holds bits of its own: a geometric number with this
                  mean, uniform columns

so that two members of a family share most of the core and differ in what
each dropped and added: their Tanimoto coefficient lies from about 0.5 to
1, by how much each has of its own. The RNG key is [seed, shard, position
of the field in the configuration]; everything is drawn in bulk, no loop a
molecule (100 M positions in seconds).

`height(spec)` is the field's height as this process draws it: `rows`,
unless the environment holds BENCH_REHEARSAL_ROWS, by which the tests of
benchmark/tests (benchmark/conftest.py) rehearse the cell at a height a
CPU walks in seconds. No run of a cell sets it, and a run that found it
set would say so in the height it prints. The environment is not in the
data's key (harness/dataset.py `data_key`): a run at a cut height takes a
`--data-root` of its own, as the tests' runs do. A `--rows` of the harness
that enters the key replaces this read (PERF.md, Open questions)."""

import os

import numpy as np

from harness import datagen

SHIP = "positions"


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {}


def height(spec: dict) -> int:
    return int(os.environ.get("BENCH_REHEARSAL_ROWS") or spec["rows"])


def _ragged_arange(lengths: np.ndarray) -> np.ndarray:
    """0 .. n-1 for every n of `lengths`, one after another."""
    ends = np.cumsum(lengths)
    return np.arange(int(ends[-1]), dtype=np.int64) - np.repeat(
        ends - lengths, lengths
    )


def family_sizes(rng, rows: int, alpha: float, largest: int) -> np.ndarray:
    """Sizes that sum to `rows`, each drawn with P(s) ~ s ** -alpha over
    1 .. largest (the last cut to fit)."""
    weights = np.arange(1, largest + 1, dtype=np.float64) ** -alpha
    cdf = np.cumsum(weights / weights.sum())
    sizes = np.empty(0, dtype=np.int64)
    while int(sizes.sum()) < rows:
        more = np.searchsorted(cdf, rng.random(max(1024, rows // 4))) + 1
        sizes = np.concatenate([sizes, np.minimum(more, largest)])
    upto = np.cumsum(sizes)
    n = int(np.searchsorted(upto, rows)) + 1
    sizes = sizes[:n].copy()
    sizes[-1] -= int(upto[n - 1]) - rows
    return sizes


def draw(config: dict, seed: int, shard: int, field: str):
    """(rows, in-shard columns) of one shard of the field: int32 arrays
    of one length, family by family; a column a member drew twice is
    listed twice."""
    spec = config["fields"][field]
    rows, width = height(spec), int(spec["width_bits"])
    rng = np.random.default_rng(
        [seed, shard, datagen.field_position(config, field)]
    )
    sizes = family_sizes(rng, rows, float(spec["family_alpha"]),
                         int(spec["family_max"]))
    n_core = np.maximum(rng.poisson(spec["core_bits"], sizes.size),
                        int(spec["core_min"])).astype(np.int64)
    core_cols = rng.integers(0, width, int(n_core.sum()), dtype=np.int32)
    core_at = np.cumsum(n_core) - n_core
    # Members, family by family; where each lies among the rows.
    family_of = np.repeat(np.arange(sizes.size, dtype=np.int32), sizes)
    row_of = rng.permutation(rows).astype(np.int32)
    # Every member's copy of its family's core, less what it drops. A
    # member's entries are neighbours, so what is the member's is
    # repeated, never gathered.
    per_member = n_core[family_of]
    which = np.repeat(
        (core_at[family_of] - (np.cumsum(per_member) - per_member)),
        per_member,
    )
    which += np.arange(which.size, dtype=np.int64)
    drop = np.minimum(rng.exponential(spec["drop_mean"], rows), 0.5)
    keep = rng.random(which.size, dtype=np.float32) >= np.repeat(
        drop.astype(np.float32), per_member
    )
    # A geometric number of bits of its own (mean own_bits, from 0).
    n_own = rng.geometric(1.0 / (1.0 + spec["own_bits"]), rows) - 1
    own_cols = rng.integers(0, width, int(n_own.sum()), dtype=np.int32)
    return (
        np.concatenate([np.repeat(row_of, per_member)[keep],
                        np.repeat(row_of, n_own)]),
        np.concatenate([core_cols[which[keep]], own_cols]),
    )
