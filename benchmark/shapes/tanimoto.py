"""The call shape `tanimoto`:
`TopN(<field>, Row(<field>=m), tanimotoThreshold=T)`, the molecules of a
library whose fingerprint is similar to molecule m's (upstream Pilosa
docs/examples.md "Chemical similarity search").

A group's keys for this shape:
  field        the set field, shipped as positions, one row a molecule
               (its draw gives `height(spec)`, the rows this process
               draws, and the field's spec states `width_bits`)
  pool         how many molecules the searches are for: a pool drawn from
               the seed, uniformly over the rows and without repeats
  thresholds   the thresholds a call draws from, uniformly

A call is (field, m, T). Its answer, as the server's JSON gives a TopN with
no `n`: `[{"id", "count"}, ...]`, every row r with c = |m AND r| > 0 and
c * 100 // |m OR r| >= T, count c, by count descending, ties by id
ascending: core/fragment.go `top`'s integer test over ALL rows of the
field, shard by shard, a row's counts of the shards in which it passes
added up.

The reference is plain numpy over the positions the seed gives and shares
no step with the program (which ANDs packed words and counts bits): per
shard the positions are indexed by column, and a pool molecule's
intersection with every row is one `bincount` over the posting lists of
its own columns; a row's size is a `bincount` of the positions' rows. The
pool is answered once a shard, at the lowest threshold of the mix, in the
loader's pool; a shard's table holds, pool molecule by pool molecule,
(row, intersection, union) of every row that passes there, so that a
call's answer is a slice, the test at the call's own threshold and a sum
over the shards. The table's height is fixed by the configuration (half
the field's rows, 524,288 at least), not by the data, so that the shards'
tables stack; a pool whose answers outgrew it would be an error, not a
cut."""

from __future__ import annotations

import threading

import numpy as np

from harness import plugins

HITS = ".hits"
POOL_KEY = 0x6D6F6C65  # "mole": the pool's own stream of the seed


def table_of(field: str, pool: int, lowest: int) -> str:
    return f"{field}{HITS}.{pool}.{lowest}"


def asked_of(name: str) -> tuple[str, int, int]:
    """(field, pool size, lowest threshold) of a table's name."""
    field, _, rest = name.rpartition(HITS + ".")
    pool, lowest = rest.split(".")
    return field, int(pool), int(lowest)


_LOADING = threading.Lock()


def height(config: dict, field: str) -> int:
    """The field's rows as its draw gives them in this process. A
    generator process's clients are threads that all ask at once, and a
    module is in `sys.modules` before it has run: one loads, the rest
    wait."""
    with _LOADING:
        draw_mod = plugins.draw_of(config, field)
    return int(draw_mod.height(config["fields"][field]))


def pool_of(seed: int, rows: int, n: int) -> np.ndarray:
    """The seed's pool: n different rows (all of them where the field has
    fewer), in the order drawn."""
    rng = np.random.default_rng([int(seed), POOL_KEY])
    return rng.permutation(rows)[: min(n, rows)].astype(np.int64)


# -- traffic ---------------------------------------------------------------


def draw(group: dict, config: dict, rng, n: int):
    """Yield the group's next n calls: a molecule of the pool and a
    threshold, both uniform, drawn all at once. The client's RNG is keyed
    [seed, client, stream] (harness/traffic.py): its first word names the
    pool."""
    seed = int(rng.bit_generator.seed_seq.entropy[0])
    field = group["field"]
    pool = pool_of(seed, height(config, field), int(group["pool"]))
    thresholds = [int(t) for t in group["thresholds"]]
    ms = pool[rng.integers(0, pool.size, n)].tolist()
    ts = rng.integers(0, len(thresholds), n).tolist()
    for m, t in zip(ms, ts):
        yield (field, m, thresholds[t])


def render(calls) -> bytes:
    return "".join(
        f"TopN({field}, Row({field}={m}), tanimotoThreshold={t})"
        for field, m, t in calls
    ).encode()


def warm(group: dict, config: dict, seed: int, client: int, send, say) -> None:
    """One request of n different calls for each n of `warm_batch_sizes`:
    a request's searches stand side by side, so they reach the backend as
    one group of n legs, which is padded to a power of two and is a
    program of its own. Every size a launch of the window can have is
    compiled here, whatever sizes the window's timing brings about."""
    sizes = [int(n) for n in group.get("warm_batch_sizes", [])]
    field = group["field"]
    pool = pool_of(seed, height(config, field), int(group["pool"]))
    thresholds = [int(t) for t in group["thresholds"]]
    for n in sizes:
        send(render([
            (field, int(pool[k % pool.size]), thresholds[k % len(thresholds)])
            for k in range(n)
        ]))
    if sizes:
        say(f"{len(sizes)} requests of {sizes} different calls")


# -- reference -------------------------------------------------------------


def tables_needed(groups: list[dict], config: dict) -> list[str]:
    """A table for every (field, pool size) of the groups, at the lowest
    threshold any of them asks: the name carries all three, because the
    mix is not in hand where a shard's tables are made."""
    lowest: dict = {}
    for g in groups:
        key = (g["field"], int(g["pool"]))
        lowest[key] = min(lowest.get(key, 100), *(int(t) for t in g["thresholds"]))
    return sorted(table_of(f, n, t) for (f, n), t in lowest.items())


def table_height(rows: int) -> int:
    return max(1 << 19, rows // 2)


def shard_tables(config: dict, names: list[str], data) -> dict:
    """{"<field>.hits.<pool>.<lowest>": int32[table_height, 4]} of one
    shard: line 0 is
    (pool size, lowest threshold, lines in use, rows), lines 1 .. pool
    are (molecule, first line, one past the last line, its own bit
    count), the rest (row, intersection, union, 0) of every row that
    passes at the lowest threshold, pool molecule by pool molecule, by
    intersection descending and row ascending."""
    out = {}
    for name in names:
        field, n_pool, lowest = asked_of(name)
        rows = height(config, field)
        pool = pool_of(data.seed, rows, n_pool)
        r, c = (np.asarray(a) for a in data.positions(field))
        # Unique (column, row) pairs in order: a column's posting list is
        # a run of `row`.
        flat = np.unique(c.astype(np.int64) * rows + r.astype(np.int64))
        col, row = (flat // rows).astype(np.int32), (flat % rows).astype(np.int32)
        del flat
        width = int(config["fields"][field]["width_bits"])
        starts = np.searchsorted(col, np.arange(width + 1))
        size = np.bincount(row, minlength=rows).astype(np.int64)
        # The pool's own columns, molecule by molecule.
        of_pool = np.flatnonzero(np.isin(row, pool))
        by_row = of_pool[np.argsort(row[of_pool], kind="stable")]
        own_rows = row[by_row]
        table = np.zeros((table_height(rows), 4), dtype=np.int32)
        at = 1 + pool.size
        for i, m in enumerate(pool.tolist()):
            mine = col[by_row[np.searchsorted(own_rows, m):
                              np.searchsorted(own_rows, m, side="right")]]
            lists = [row[starts[j]: starts[j + 1]] for j in mine.tolist()]
            inter = np.bincount(
                np.concatenate(lists) if lists else np.empty(0, np.int32),
                minlength=rows,
            )
            # c * 100 // union >= T needs c * 100 >= T * |m| at least (the
            # union holds m): most rows share a bit or two with m, few
            # this many.
            cand = np.flatnonzero(
                inter >= max(1, -(-lowest * int(size[m]) // 100))
            )
            union = size[cand] + size[m] - inter[cand]
            keep = inter[cand] * 100 // union >= lowest
            cand, got, union = cand[keep], inter[cand][keep], union[keep]
            order = np.lexsort((cand, -got))
            end = at + cand.size
            if end > table.shape[0]:
                raise ValueError(
                    f"{name}: the pool's answers outgrow {table.shape[0]} lines"
                )
            table[at:end, 0] = cand[order]
            table[at:end, 1] = got[order]
            table[at:end, 2] = union[order]
            table[1 + i] = (m, at, end, size[m])
            at = end
        table[0] = (pool.size, lowest, at, rows)
        out[name] = table
    return out


def over_shards(tables: dict) -> dict:
    """The shards' tables side by side, int32[shards, lines, 4]: the test
    is a shard's own, so nothing of them can be added up beforehand."""
    return tables


_INDEX: dict = {}


def _lines_of(name: str, table: np.ndarray) -> dict:
    """{molecule: the line that names it}, made once a table (every
    shard's table names the pool in the same lines) and kept with the
    table it was made from, so that another seed's table under the same
    name is never read by this one's lines."""
    kept = _INDEX.get(name)
    if kept is None or kept[0] is not table:
        n = int(table[0, 0, 0])
        kept = (table, {int(m): 1 + i for i, m in enumerate(table[0, 1: 1 + n, 0])})
        _INDEX[name] = kept
    return kept[1]


def answer(config: dict, totals: dict, call):
    field, m, t = call
    for name, table in totals.items():
        if name.startswith(field + HITS + ".") and t >= int(table[0, 0, 1]):
            line = _lines_of(name, table).get(int(m))
            if line is not None:
                break
    else:
        raise ValueError(f"no table answers {call!r}")
    counts: dict = {}
    for shard in table:
        _, lo, hi, _ = shard[line].tolist()
        hits = shard[lo:hi]
        passing = hits[hits[:, 1].astype(np.int64) * 100 >= t * hits[:, 2].astype(np.int64)]
        if len(table) == 1:
            # One shard: the lines are in the answer's order already.
            return [{"id": r, "count": k} for r, k in passing[:, :2].tolist()]
        for r, k in passing[:, :2].tolist():
            counts[r] = counts.get(r, 0) + k
    return [{"id": r, "count": k}
            for r, k in sorted(counts.items(), key=lambda rk: (-rk[1], rk[0]))]


def compare(got, want):
    """(the returned result equals the reference's; the largest absolute
    difference of a row's count, a row that one side leaves out counting
    its whole count, where the result has the form of an answer)."""
    try:
        have = {int(p["id"]): int(p["count"]) for p in got}
        ref = {int(p["id"]): int(p["count"]) for p in want}
    except (KeyError, TypeError, ValueError):
        return False, None
    err = max(
        (abs(have.get(k, 0) - ref.get(k, 0)) for k in have.keys() | ref.keys()),
        default=0,
    )
    return got == want, err
