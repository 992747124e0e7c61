"""The one general traffic generator: a mix's data file + the query templates
+ the seed -> the pool of distinct queries and each client's walk over it.
Imports numpy only."""

import numpy as np

from . import reference

WALK_ROUNDS = 512


def draw_holes(template: dict, tables: dict, rng) -> dict:
    holes = {}
    for h in template["holes"]:
        if "column" in h:
            table = tables[h["column"]]
            v = table[int(rng.integers(0, len(table)))]
            v = str(v) if table.dtype.kind in "US" else int(v)
        elif "int" in h:
            v = int(rng.integers(h["int"][0], h["int"][1] + 1))
        elif "choice" in h:
            v = h["choice"][int(rng.integers(0, len(h["choice"])))]
        elif "add" in h:
            v = holes[h["add"][0]] + h["add"][1]
        elif "format" in h:
            v = h["format"].format(**holes)
        else:
            raise ValueError(f"hole {h} of {template['name']}: no domain")
        holes[h["name"]] = v
    return holes


def build_pool(traffic: dict, templates: list, tables: dict, seed: int) -> list:
    """`variants_per_template` distinct queries of every template, or as many
    as its domains hold: [{"template", "variant", "sql", "spec"}], spec bound
    to its literals."""
    pool = []
    for ti, t in enumerate(templates):
        seen = set()
        rng = np.random.default_rng([seed, 7, ti])
        for v in range(int(traffic["variants_per_template"])):
            for _ in range(1000):
                holes = draw_holes(t, tables, rng)
                sql = t["sql"].format(**holes)
                if sql not in seen:
                    break
            else:
                break       # the template's domains hold no more queries
            seen.add(sql)
            pool.append({"template": t["name"], "variant": v, "sql": sql,
                         "spec": reference.bind(t["reference"], holes)})
    return pool


def client_walks(traffic: dict, pool: list, seed: int) -> list:
    """The pool indexes to send, in order: rounds over all the templates, each
    round shuffled from the seed, the variant stepping with the round, so every
    seed offers the same work in another order. One walk a client; where the
    mix says `"queue": "shared"`, one walk in all, from which every client
    takes the next query as its last one is answered (a connection pool that
    drains one queue of dashboard refreshes), so that any stretch of the window
    holds whole rounds and at most one part of a round, not one part a client."""
    by_template = {}
    for i, p in enumerate(pool):
        by_template.setdefault(p["template"], []).append(i)
    variants = list(by_template.values())
    shared = traffic.get("queue") == "shared"
    walks = []
    for c in range(1 if shared else int(traffic["clients"])):
        rng = np.random.default_rng([seed, 11, c])
        walk = []
        for r in range(WALK_ROUNDS):
            for ti in rng.permutation(len(variants)):
                walk.append(variants[ti][(r + c) % len(variants[ti])])
        walks.append(walk)
    return walks
