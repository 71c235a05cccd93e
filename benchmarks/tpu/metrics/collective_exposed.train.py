"""Share of the traced window (%) in which a device runs a collective
(all-gather, reduce-scatter, all-reduce, collective-permute, all-to-all,
their async starts and dones, and fusions built round one) while no other
op of the same device runs, averaged over the devices.

The trace names ops alone; the compiled step's text says which are
collectives (by opcode, or by the computation a fusion calls) and which
are loops or calls that merely hold other ops (``while``, ``conditional``,
``call``), which overlap nothing of their own.  Where the step has no
collective, as on one chip, there is nothing to read."""
import re

import devtrace as trace

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
         "all-to-all", "async-collective")
HOLDERS = ("while", "conditional", "call")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def _is_kind(name: str) -> bool:
    return name.startswith(KINDS)


def classify(hlo_text: str):
    """(collective instruction names, holder instruction names)."""
    coll, holders = set(), set()
    for line in hlo_text.splitlines():
        m = _LINE.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        called = _CALLS.findall(line)
        if (_is_kind(opcode) or _is_kind(name)
                or any(_is_kind(c) for c in called)):
            coll.add(name)
        elif opcode in HOLDERS:
            holders.add(name)
    return coll, holders


def exposed_ns(events, coll, holders, window_ns: float) -> float:
    """Time in which a collective runs and no other op does."""
    mine = trace._union(((e[1], e[1] + e[2]) for e in events if e[0] in coll),
                        0, window_ns)
    other = trace._union(((e[1], e[1] + e[2]) for e in events
                          if e[0] not in coll and e[0] not in holders),
                         0, window_ns)
    total, j = 0.0, 0
    for s, e in mine:                  # both lists sorted and disjoint
        total += e - s
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            total -= min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return total


def read(ctx):
    rec = ctx["trace"]
    if not rec["devices"] or not ctx["counts"].get("steps"):
        return None
    coll, holders = classify(ctx["hlo"]())
    if not coll:
        return None
    w = rec["window_ns"]
    per = [exposed_ns(evs, coll, holders, w) for evs in rec["devices"].values()]
    return 100.0 * sum(per) / len(per) / w
