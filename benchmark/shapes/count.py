"""The call shape `count`: `Count(<verb>(Row(a=i), Row(b=j)[, Row(c=k)]))`
over up to three distinct set fields, the whole index.

A group's keys for this shape:
  verbs              the verbs a call draws from, uniformly
  operand_sets       lists of set fields; a call draws one uniformly,
                     then a row of each field uniformly
  warm_batch_sizes   optional: the numbers of same-verb calls that one
                     launch can hold, each of which the warm-up sends
                     once as one request, verb by verb

A call is (verb, [(field, row), ...]); its answer is one integer.

The reference is plain numpy over the bits the seed gives, nothing of the
program (never exec/cpu.py, no roaring, no tables the server made). Per
shard it counts, with AND and popcount alone, the intersection of every
combination of rows of the fields of an operand set and of each of its
subsets: the *intersection tables* (int64[rows_a, rows_b, ...] a shard),
summed over the index. The answer then follows by inclusion and
exclusion, which `answer` spells out and benchmark/tests/test_reference.py
checks against the verbs applied bit by bit on hand-worked shards.
"""

from __future__ import annotations

import itertools

import numpy as np

from harness import datagen

VERBS = ("Intersect", "Union", "Difference", "Xor")
MAX_OPERANDS = 3


# -- traffic ---------------------------------------------------------------


def draw(group: dict, config: dict, rng, n: int):
    """Yield the group's next n calls. The random numbers of all n are
    drawn at once; a call is built when it is taken, so that the cost is
    spread evenly over a client's requests."""
    rows = {f: spec["rows"] for f, spec in config["fields"].items()
            if spec["type"] == "set"}
    verbs = list(group["verbs"])
    sets = [list(s) for s in group["operand_sets"]]
    width = max(len(s) for s in sets)
    set_i = rng.integers(0, len(sets), n).tolist()
    verb_i = rng.integers(0, len(verbs), n).tolist()
    # One uniform draw per operand slot, scaled to the field's rows.
    u = rng.random((n, width)).tolist()
    for k in range(n):
        uk = u[k]
        yield (
            verbs[verb_i[k]],
            [(f, int(uk[j] * rows[f])) for j, f in enumerate(sets[set_i[k]])],
        )


def render(calls) -> bytes:
    return "".join(
        f"Count({verb}(" + ", ".join(f"Row({f}={r})" for f, r in leaves) + "))"
        for verb, leaves in calls
    ).encode()


def narrowed(group: dict, verbs: list[str]) -> dict:
    """The group with its verbs cut down to those of `verbs`."""
    return dict(group, verbs=[v for v in group["verbs"] if v in verbs])


def distinct_calls(group: dict, config: dict, seed: int, client: int,
                   verb: str, n: int) -> list:
    """n different calls of one verb, drawn as the group's traffic is
    (the client's stream 1, the verbs narrowed to the one)."""
    rng = np.random.default_rng([seed, client, 1])
    calls: dict = {}
    for _ in range(64):
        for v, leaves in draw(narrowed(group, [verb]), config, rng, 512):
            calls.setdefault((v, tuple(leaves)), (v, leaves))
            if len(calls) >= n:
                return list(calls.values())
    raise ValueError(f"the group's pool holds fewer than {n} calls of {verb}")


def warm(group: dict, config: dict, seed: int, client: int, send, say) -> None:
    """For a group that states `warm_batch_sizes`: for each of its verbs
    and each size n, one request of n different calls of that verb. The
    batcher pads a group of concurrent calls of one shape to a power of
    two, and each (verb, padded size) is a program of its own; one
    request of n calls reaches the backend as one group of n, so every
    program the window can need is compiled here, whatever sizes the
    window's timing then brings about."""
    sizes = group.get("warm_batch_sizes", [])
    for verb in group["verbs"]:
        for n in sizes:
            send(render(distinct_calls(group, config, seed, client, verb, int(n))))
    if sizes:
        say(f"batches of {sizes} calls of each verb")


# -- reference -------------------------------------------------------------


def popcount(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=-1, dtype=np.int64)


def table_key(fields) -> str:
    return "|".join(fields)


def tables_needed(groups: list[dict], config: dict) -> list[str]:
    """The intersection tables these groups' answers read: one for every
    non-empty subset of each operand set, its fields in the
    configuration's order. No other field of the configuration is read."""
    order = datagen.set_fields(config)
    names = set()
    for g in groups:
        for operands in g["operand_sets"]:
            if len(operands) > MAX_OPERANDS:
                raise ValueError(f"more than {MAX_OPERANDS} operands: {operands}")
            fields = sorted(set(operands), key=order.index)
            for n in range(1, len(fields) + 1):
                names.update(table_key(c) for c in itertools.combinations(fields, n))
    return sorted(names, key=lambda k: (k.count("|"), [order.index(f) for f in k.split("|")]))


def shard_tables(config: dict, names: list[str], data) -> dict:
    """{name: int64[rows_a, rows_b, ...]} of one shard. `data.bits(field)`
    is the field's bool[rows, shard_width]."""
    words: dict = {}

    def w(field):
        if field not in words:
            words[field] = datagen.pack64(data.bits(field))
        return words[field]

    out = {}
    for name in names:
        combo = name.split("|")
        if len(combo) == 1:
            out[name] = popcount(w(combo[0]))
        elif len(combo) == 2:
            a, b = (w(c) for c in combo)
            out[name] = popcount(a[:, None, :] & b[None, :, :])
        else:
            a, b, c = (w(x) for x in combo)
            out[name] = np.stack(
                [popcount(a[:, None, :] & (b & cr[None, :])[None, :, :])
                 for cr in c],
                axis=-1,
            )
    return out


def intersection(config: dict, totals: dict, leaves) -> int:
    """|Row(a=i) & Row(b=j) & ...| for leaves [(field, row), ...] of
    distinct fields, in any order."""
    order = list(config["fields"])
    leaves = sorted(leaves, key=lambda fr: order.index(fr[0]))
    fields = [f for f, _ in leaves]
    if len(set(fields)) != len(fields):
        raise ValueError(f"operands repeat a field: {fields}")
    return int(totals[table_key(fields)][tuple(r for _, r in leaves)])


def answer(config: dict, totals: dict, call) -> int:
    """Count(<verb>(leaves...)), by inclusion and exclusion over the
    intersections of the operands' subsets T:
      Intersect  = I(all)
      Union      = sum over nonempty T of (-1)^(|T|+1) I(T)
      Difference = first minus the union of the rest
                 = sum over T of the rest of (-1)^|T| I({first} + T)
      Xor        = sum over nonempty T of (-2)^(|T|-1) I(T)
    `totals` are the tables summed over the index's shards.
    """
    verb, leaves = call
    leaves = [tuple(x) for x in leaves]

    def inter(t):
        return intersection(config, totals, t)

    if verb == "Intersect" or len(leaves) == 1:
        return inter(leaves)
    n = len(leaves)
    total = 0
    if verb == "Union":
        for k in range(1, n + 1):
            for t in itertools.combinations(leaves, k):
                total += (-1) ** (k + 1) * inter(t)
    elif verb == "Xor":
        for k in range(1, n + 1):
            for t in itertools.combinations(leaves, k):
                total += (-2) ** (k - 1) * inter(t)
    elif verb == "Difference":
        first, rest = leaves[0], leaves[1:]
        for k in range(0, n):
            for t in itertools.combinations(rest, k):
                total += (-1) ** k * inter((first,) + t)
    else:
        raise ValueError(f"no reference for verb {verb!r}")
    return total


def compare(got, want: int):
    """(the returned result equals the reference's, the absolute error
    where the result is a number)."""
    if isinstance(got, int) and not isinstance(got, bool):
        return got == want, abs(got - want)
    return False, None


def direct_answer(verb: str, rows: list[np.ndarray]) -> int:
    """The verb applied bit by bit to bool rows: what `answer` must equal.
    Used by the tests, at sizes where it is cheap."""
    out = rows[0].copy()
    for r in rows[1:]:
        if verb == "Intersect":
            out &= r
        elif verb == "Union":
            out |= r
        elif verb == "Difference":
            out &= ~r
        elif verb == "Xor":
            out ^= r
        else:
            raise ValueError(verb)
    return int(out.sum())
