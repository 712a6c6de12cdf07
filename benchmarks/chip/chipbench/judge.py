"""The comparison that decides ``correct``.

Each answered request becomes an *item*: the program's answer, the
objective and SRAM budget it was asked under, the ``evaluated`` count the
reference expects, the request's space ``bounds`` (prefix cuts and the
choices of each remaining run) and whether the reference scans that space
whole to check the argmin (``scan``).  The reference prices the program's
cut tuple and every winner row the device produced, and scans the chosen
spaces.  The numbers
compared, each against its limit (readings in PERF.md §2):

* ``wrong_answers`` -- requests where an exact quantity differs: the cut
  tuple lies outside the request's space; an integer metric, feasibility
  or ``evaluated`` differs from the reference's for that tuple (a
  compile's plan totals too); a device row is missing, malformed, ranks
  feasibility otherwise than the reference, or the winning row does not
  decode to the returned tuple; or the reference's first minimum ties the
  returned tuple's key but comes earlier in product order.  Limit 0.
* ``key_gap`` -- the widest relative gap between a float the program gave
  (each device row's primary and secondary key, the returned latency, a
  compile's plan latency) and the reference's value for the same tuple.
* ``regret`` -- over the scanned spaces, the widest relative gap by which
  the reference's key of the returned tuple lies above the reference's
  least key there (primary first, then secondary on a tie; 1.0 when it is
  infeasible and a feasible one exists).
* ``nothing_checked`` -- 1 when no request was answered or no argmin was
  scanned.  Limit 0.
"""
from __future__ import annotations

import math
import os

from chipbench import reference

# Limits, set from the readings in PERF.md §2: sound runs read key_gap
# up to ~4e-15 (a compile's plan latency is a compensated sum) and regret 0;
# float32 keys on the device read key_gap ~1e-8 and more, a fold that
# drops half of the launches reads regret ~1e-3 and more.
LIMITS = {"wrong_answers": 0, "key_gap": 1e-11, "regret": 1e-9,
          "nothing_checked": 0}
FIELDS = ("dram_total", "dram_fm", "sram_total", "bram18k")


def rel_gap(got, want) -> float:
    got, want = float(got), float(want)
    if not (math.isfinite(got) and math.isfinite(want)):
        return 0.0 if got == want else math.inf
    return abs(got - want) / max(abs(want), 1.0)


def regret(key, best) -> float:
    if key[0] != best[0]:
        return 1.0 if key[0] > best[0] else 0.0
    q = 1 if key[1] != best[1] else 2
    return max(0.0, (key[q] - best[q]) / max(abs(best[q]), 1.0))


def in_space(cuts, prefix, dims) -> bool:
    n = len(prefix)
    return (len(cuts) == n + len(dims) and list(cuts[:n]) == list(prefix)
            and all(0 <= c < d for c, d in zip(cuts[n:], dims)))


def _row_index(r) -> int | None:
    row = r["row"]
    if row is None or len(row) != 4 or not math.isfinite(row[3]):
        return None
    j = row[3]
    if j != int(j) or not 0 <= j < reference.space_size(r["dims"]):
        return None
    return int(j)


def judge(cfg: dict, items: list, rows_expected: bool,
          workers: int | None = None, log=print) -> dict:
    """``{name: (value, limit)}`` over ``items`` (see the module doc)."""
    cuts_set: dict = {}
    for it in items:
        cuts_set.setdefault(tuple(it["answer"]["cuts"]), None)
        for r in it["answer"].get("device_rows", []):
            j = _row_index(r)
            if j is not None:
                cuts_set.setdefault(reference.decode(r["prefix"], r["dims"],
                                                     j), None)
    spaces: dict = {}
    for it in items:
        if it["scan"]:
            prefix, dims = it["bounds"]
            qs = spaces.setdefault((tuple(prefix), tuple(dims)), [])
            q = (it["objective"], int(it["budget"]))
            if q not in qs:
                qs.append(q)

    n_workers = workers or max(1, (os.cpu_count() or 2) - 1)
    cuts_list = list(cuts_set)
    step = max(1, math.ceil(len(cuts_list) / n_workers))
    jobs = [("price", (cfg, cuts_list[i:i + step]))
            for i in range(0, len(cuts_list), step)]
    n_price = len(jobs)
    spans = []
    for (prefix, dims), qs in spaces.items():
        sj = reference.scan_jobs(cfg, prefix, dims, qs, n_workers)
        spans.append(((prefix, dims), qs, len(jobs), len(jobs) + len(sj)))
        jobs += sj
    results = reference.run_jobs(jobs, workers)
    prices = dict(zip(cuts_list,
                      [p for res in results[:n_price] for p in res]))
    best = {}
    for (space, qs, a, b) in spans:
        for q, kj in zip(qs, reference.merge_scans(results[a:b])):
            best[space + (q,)] = kj

    wrong, key_gap, worst = 0, 0.0, 0.0
    for it in items:
        why = _compare(it, prices, best, rows_expected)
        key_gap = max(key_gap, why.pop("key_gap"))
        worst = max(worst, why.pop("regret"))
        if why["wrong"]:
            wrong += 1
            if wrong <= 3:
                log(f"request {it['req']['id']} ({it['objective']}, budget "
                    f"{it['budget']}) differs from the reference: "
                    f"{'; '.join(why['wrong'])}")
    return {"wrong_answers": (wrong, LIMITS["wrong_answers"]),
            "key_gap": (key_gap, LIMITS["key_gap"]),
            "regret": (worst, LIMITS["regret"]),
            "nothing_checked": (0 if items and spaces else 1,
                                LIMITS["nothing_checked"])}


def _compare(it, prices, best, rows_expected) -> dict:
    a, obj, budget = it["answer"], it["objective"], int(it["budget"])
    cuts = tuple(a["cuts"])
    p = prices[cuts]
    wrong, gap, rg = [], 0.0, 0.0
    if p is None:
        return {"wrong": [f"{list(cuts)} is no cut tuple"], "key_gap": gap,
                "regret": rg}
    if not in_space(cuts, *it["bounds"]):
        wrong.append(f"cuts {list(cuts)} outside the request's space")
    for f in FIELDS:
        if a[f] != getattr(p, f):
            wrong.append(f"{f} {a[f]} != reference {getattr(p, f)}")
    if a["feasible"] != p.feasible(budget):
        wrong.append(f"feasible {a['feasible']} != reference "
                     f"{p.feasible(budget)}")
    if a["evaluated"] != it["evaluated"]:
        wrong.append(f"evaluated {a['evaluated']} != {it['evaluated']}")
    gap = max(gap, rel_gap(a["latency_cycles"], p.latency_cycles))
    if "plan_latency_cycles" in a:
        gap = max(gap, rel_gap(a["plan_latency_cycles"], p.latency_cycles))
        if a["plan_dram_total"] != p.dram_total:
            wrong.append("plan DRAM total differs")
        if a["plan_sram_total"] != p.sram_total:
            wrong.append("plan SRAM total differs")

    rows = a.get("device_rows", [])
    if rows_expected and not rows:
        wrong.append("no device row")
    winner = None
    for r in rows:
        j = _row_index(r)
        if j is None or r["objective"] != obj:
            wrong.append(f"malformed device row {r['row']}")
            continue
        rc = reference.decode(r["prefix"], r["dims"], j)
        k = prices[rc].key(obj, budget)
        rank, pk, sk, _ = r["row"]
        if rank != k[0]:
            wrong.append(f"device ranks {list(rc)} {rank}, reference {k[0]}")
        gap = max(gap, rel_gap(pk, k[1]), rel_gap(sk, k[2]))
        if winner is None or (rank, pk, sk, rc) < winner:
            winner = (rank, pk, sk, rc)
    if winner is not None and winner[3] != cuts:
        wrong.append(f"device winner {list(winner[3])} != returned "
                     f"{list(cuts)}")

    if it["scan"]:
        prefix, dims = it["bounds"]
        kb, jb = best[(tuple(prefix), tuple(dims), (obj, budget))]
        k = p.key(obj, budget)
        rg = regret(k, kb)
        first = reference.decode(prefix, dims, jb)
        if k == kb and first != cuts:
            wrong.append(f"reference's first minimum {list(first)} ties "
                         f"and precedes {list(cuts)}")
    return {"wrong": wrong, "key_gap": gap, "regret": rg}
