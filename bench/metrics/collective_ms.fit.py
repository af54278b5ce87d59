"""Device milliseconds a federation spends in cross-chip collectives.

Per chip, the operations of the traced window whose own name is an
``all-reduce`` or an ``all-gather`` (XLA's names for a ``psum`` and an
``all_gather``): a synchronous one counts its own interval, an
asynchronous pair (``all-reduce-start`` … ``all-reduce-done``) the
interval from the start's beginning to the done's end, each done paired
with the start it names among its operands, else the earliest open one.
The union of those intervals per chip, averaged over the chips that ran
one, over the federations in the window. A collective waits for the
slowest chip to arrive, so the time holds what each round's
synchronisation of the shards costs, not only the transfer. DEM's
all-reduce carries a few kilobytes (the statistics, 6,768 B at K = 10,
d = 84) and is bound by latency: a share of the interconnect's bandwidth
would mean nothing, so none is read. Silent where no chip ran a
collective."""
import re

from lib.trace import busy_ns, op_name

KINDS = ("all-reduce", "all-gather")


def _kind(name: str):
    """(collective, phase) of an operation's own name: phase is "start",
    "done" or "" (synchronous); None for any other operation."""
    base = name.lstrip("%").split(".", 1)[0]
    for kind in KINDS:
        if base == kind:
            return kind, ""
        if base in (kind + "-start", kind + "-done"):
            return kind, base[len(kind) + 1:]
    return None


def collective_intervals(ops) -> list:
    """(name, start_ns, end_ns) of one chip's collectives, pairs joined."""
    out, open_starts = [], {}
    for event, a, b in sorted(ops, key=lambda op: op[1]):
        name = op_name(event)
        kind = _kind(name)
        if kind is None:
            continue
        coll, phase = kind
        if not phase:
            out.append((name, a, b))
        elif phase == "start":
            open_starts.setdefault(coll, []).append((name, a))
        else:
            starts = open_starts.get(coll, [])
            operands = set(re.findall(r"%([\w.\-]+)",
                                      event.split(" = ", 1)[-1]))
            named = [i for i, (s, _) in enumerate(starts)
                     if s.lstrip("%") in operands]
            if starts:
                _, a = starts.pop(named[0] if named else 0)
            out.append((name, a, b))
    return out


def read(layer):
    fits = layer.get("fits")
    if not fits:
        return None
    lo, hi = layer["lo"], layer["hi"]
    per_chip = [busy_ns(collective_intervals(ops), lo, hi)
                for ops in layer["trace"].device_ops.values()]
    per_chip = [ns for ns in per_chip if ns]
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) / 1e6 / len(fits)
