"""From a profiler trace to numbers: device busy time, time by kernel scope,
the operations that took most time, and the longest idle gaps named by what
the host was doing.

``read_xplane`` turns the profiler's ``.xplane.pb`` into plain rows
(``jax.profiler.ProfileData``, nothing but JAX); everything after that works
on the rows, so the tests run it on a small recorded slice kept as JSON.

What a v5e trace looks like (read by hand, PR 26): one plane per chip named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
instruction, named by the instruction's whole text (``%fusion.6 = bf16[..]
fusion(...)``); a Pallas kernel is a ``custom-call`` whose instruction name
is the ``jax.named_scope`` it was traced under (``%flash_attention.144``),
and that name is all the trace says of scopes -- events carry no ``tf_op``
stat.  ``Async XLA Ops`` holds copies that overlap the ops and is not
counted as busy time.  The line ``XLA Modules`` holds one event per executed
program.  Host threads are lines of the plane ``/host:CPU``;
``jax.profiler.TraceAnnotation`` spans appear there under their own names
(the harness's start with ``bench:``), on the same clock.
"""

import glob
import gzip
import json
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench:"


# ------------------------------------------------------------ reading a trace
def find_xplane(directory):
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(instruction):
    """``%fusion.6 = bf16[8,2048]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.6 bf16[8,2048]``: the instruction's own name and its result
    type without layouts.  A name that is no HLO text is kept whole."""
    head, sep, rest = instruction.partition(" = ")
    if not sep:
        return instruction[:96]
    rest = _LAYOUT.sub("", rest)
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):
        depth += ch in "([" 
        depth -= ch in ")]"
        if ch == " " and depth == 0:
            end = i
            break
    return (head.lstrip("%") + " " + rest[:end])[:96]


def instruction_kind(name):
    """``flash_attention.144 (bf16[..])`` -> ``flash_attention``: a short
    name without the instruction's number and result type."""
    head = name.split(" ", 1)[0]
    base, dot, number = head.rpartition(".")
    return base if dot and number.isdigit() else head


def read_xplane(path):
    """-> {"devices": {plane name: {"ops": rows, "modules": rows}},
           "host": rows}; a row is [short name, start_ns, dur_ns]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append([short_name(ev.name), int(ev.start_ns),
                                     int(ev.duration_ns)])
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def save_rows(rows, path, max_ops=None):
    """Keep a (slice of a) trace as JSON rows: the tests' fixture."""
    if max_ops is not None:
        rows = {"devices": {k: {"ops": v["ops"][:max_ops],
                                "modules": v["modules"][:8]}
                            for k, v in rows["devices"].items()},
                "host": rows["host"][:64]}
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(rows, f)


def load_rows(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------- the reduction
def union_length(intervals):
    """Total length covered by (start, end) intervals, overlaps once."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The uncovered stretches of [lo, hi] -> list of (start, end)."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class Reduced:
    """A trace reduced over the chips used; all times in seconds."""

    def __init__(self, rows, chips=1):
        names = sorted(rows["devices"])[:chips]
        if not names:
            raise ValueError("the trace holds no device plane")
        self.devices = [rows["devices"][n] for n in names]
        self.host = rows["host"]
        starts = [op[1] for d in self.devices for op in d["ops"]]
        ends = [op[1] + op[2] for d in self.devices for op in d["ops"]]
        if not starts:
            raise ValueError("no operation ran on the device in the trace")
        # the traced window as the device saw it: first op start to last
        # op end over all chips (the profiler's own start is not in the file)
        self.t_lo, self.t_hi = min(starts), max(ends)
        self.window_s = (self.t_hi - self.t_lo) / 1e9
        busy = [union_length([(op[1], op[1] + op[2]) for op in d["ops"]])
                for d in self.devices]
        self.busy_s = sum(busy) / len(busy) / 1e9

    @property
    def idle_pct(self):
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def scope_events(self, scope):
        """Events of the instructions named after ``scope`` (a kernel traced
        under ``jax.named_scope(scope)`` is ``<scope>.<n>``) -> [(start_ns,
        dur_ns)] on the first chip."""
        return [(op[1], op[2]) for op in self.devices[0]["ops"]
                if instruction_kind(op[0]) == scope]

    def scope_seconds(self, scope):
        """Summed device time of the events under ``scope`` (first chip)."""
        return sum(d for _, d in self.scope_events(scope)) / 1e9

    def module_runs(self):
        """How many programs ran on the first chip in the traced window."""
        return len(self.devices[0]["modules"])

    def top_ops(self, k=10):
        """The operations with most device time on the first chip; calls of
        one kind and result type (a layer's fusion in every layer, a kernel
        in every call) are summed under the name without its number."""
        total = {}
        for op in self.devices[0]["ops"]:
            head, sep, result_type = op[0].partition(" ")
            kind = instruction_kind(head) + sep + result_type
            total[kind] = total.get(kind, 0) + op[2]
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in ranked]

    def idle_gaps(self, k=10):
        """The longest idle gaps of the first chip, each named by the
        harness span that covers most of it on the host."""
        ops = [(op[1], op[1] + op[2]) for op in self.devices[0]["ops"]]
        found = sorted(gaps(ops, self.t_lo, self.t_hi),
                       key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in found:
            best, best_cover = "host:unattributed", 0
            for name, hs, hd in self.host:
                cover = min(e, hs + hd) - max(s, hs)
                if cover > best_cover:
                    best, best_cover = name[len(HOST_SPAN_PREFIX):], cover
            out.append([best, (e - s) / 1e9])
        return out

    def breakdown(self):
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}

    def summary(self):
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "idle_pct": self.idle_pct, "chips": len(self.devices),
                "ops": sum(len(d["ops"]) for d in self.devices),
                "module_runs": self.module_runs()}


def reduce_dir(directory, chips=1):
    return Reduced(read_xplane(find_xplane(directory)), chips=chips)
