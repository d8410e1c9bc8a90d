"""Reduction of a profiler trace (``.xplane.pb``) to what the layer readers
and the last line's ``device`` and ``breakdown`` need. Nothing but
``jax.profiler.ProfileData``.

What a TPU trace holds (looked at by hand first, ``tools/dump_trace.py``): a
plane per device (``/device:TPU:N``) whose line ``XLA Modules`` has one event
per executable run, named ``jit_<fn>(<fingerprint>)`` with a ``run_id``, and
whose line ``XLA Ops`` has one event per HLO instruction run, named by the
instruction's full text (result type, opcode, operand types). The host plane
(``/host:CPU``) holds the benchmark's own spans (``TraceAnnotation``) by name.

Attribution of module runs to the program's labels goes by order: the k-th
span the benchmark put round a compiled call is the k-th module run on the
device (one device queue, runs in dispatch order). Host and device clocks
differ by about a millisecond, so times are never used for it. Every run of
one executable must land on one label, or the reduction raises.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

_CONTAINERS = {"while", "conditional", "call", "async-start", "async-done",
               "copy-start", "copy-done"}
_TYPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def parse_instruction(text: str):
    """``%name.3 = bf16[8,128]{...} opcode(bf16[8,64]{...} %a, ...)`` ->
    (name without its number, opcode, result (dtype, dims) or None,
    [operand (dtype, dims), ...])."""
    m = re.match(r"%?([\w\-]+?)(?:\.\d+)* = ", text)
    if not m:
        return text.split("(")[0].strip("% "), "", None, []
    name, rest = m.group(1), text[m.end():]
    if rest.startswith("("):  # a tuple result: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = None, rest[i + 1:].lstrip()
    else:
        head, _, rest = rest.partition(" ")
        t = _TYPE.match(head)
        result = (t.group(1), _dims(t.group(2))) if t else None
    opcode, _, args = rest.partition("(")
    args = args.split("), ")[0] if ")," in args else args
    operands = [(d, _dims(s)) for d, s in _TYPE.findall(args)]
    return name, opcode.strip(), result, operands


def _dims(text: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in text.split(",") if x)


def short_name(text: str) -> str:
    """``int8_matmul_bf16_104_18944_``: the op and its result, for the
    breakdown."""
    name, _, result, _ = parse_instruction(text)
    if result is None:
        return name
    return f"{name}_{result[0]}_" + "".join(f"{d}_" for d in result[1])


class Trace:
    """A trace, reduced once. A decode loop leaves a million and more tiny
    op events in a few seconds but only a few thousand distinct
    instructions, so events are kept as arrays (instruction id, start,
    duration) and every instruction's text is parsed once."""

    def __init__(self, path: str, span_labels: Iterable[str]):
        import numpy as np
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.devices: List[dict] = []
        self.host_spans: List[Tuple[str, float, float]] = []
        self.host_events: List[Tuple[str, float, float]] = []
        labels = set(span_labels)
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = {"name": plane.name, "modules": [], "texts": [],
                       "op_id": np.zeros(0, np.int64),
                       "op_start": np.zeros(0), "op_dur": np.zeros(0)}
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        for ev in line.events:
                            run_id = dict(ev.stats).get("run_id")
                            dev["modules"].append(
                                (ev.name, ev.start_ns, ev.duration_ns,
                                 int(run_id) if run_id is not None else None))
                    elif line.name == "XLA Ops":
                        index: Dict[str, int] = {}
                        ids, starts, durs = [], [], []
                        for ev in line.events:
                            ids.append(index.setdefault(ev.name, len(index)))
                            starts.append(ev.start_ns)
                            durs.append(ev.duration_ns)
                        dev.update(texts=list(index),
                                   op_id=np.asarray(ids, np.int64),
                                   op_start=np.asarray(starts, np.float64),
                                   op_dur=np.asarray(durs, np.float64))
                dev["parsed"] = [parse_instruction(t) for t in dev["texts"]]
                n = len(dev["texts"])
                dev["seconds"] = np.bincount(
                    dev["op_id"], weights=dev["op_dur"], minlength=n) / 1e9
                dev["count"] = np.bincount(dev["op_id"], minlength=n)
                self.devices.append(dev)
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in labels:
                            self.host_spans.append(
                                (ev.name, ev.start_ns, ev.duration_ns))
                        elif not ev.name.startswith("$"):
                            self.host_events.append(
                                (ev.name, ev.start_ns, ev.duration_ns))
        self.host_spans.sort(key=lambda e: e[1])
        if not self.devices:
            raise ValueError(f"{path} holds no /device:TPU plane")
        self._label_modules()

    # -- attribution ---------------------------------------------------------

    def _label_modules(self) -> None:
        """``module_label``: executable name -> program label, by order."""
        self.module_label: Dict[str, str] = {}
        dev = self.devices[0]
        runs = sorted(dev["modules"],
                      key=lambda m: (m[3] if m[3] is not None else 0, m[1]))
        if len(runs) != len(self.host_spans):
            raise ValueError(
                f"{len(self.host_spans)} spans round compiled calls but "
                f"{len(runs)} module runs on the device: something ran "
                "outside the spans, or the trace cut a run in two")
        for (label, _, _), (name, _, _, _) in zip(self.host_spans, runs):
            if self.module_label.setdefault(name, label) != label:
                raise ValueError(
                    f"executable {name} ran under both "
                    f"{self.module_label[name]!r} and {label!r}")

    # -- sums ------------------------------------------------------------------

    def busy_seconds(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        import numpy as np

        total = 0.0
        for dev in self.devices:
            if not len(dev["op_start"]):
                continue
            order = np.argsort(dev["op_start"], kind="stable")
            start = dev["op_start"][order]
            end = np.maximum.accumulate(start + dev["op_dur"][order])
            # time covered = each event's end beyond everything before it
            before = np.concatenate([[start[0]], end[:-1]])
            total += float(np.sum(end - np.maximum(start, before))) / 1e9
        return total / len(self.devices)

    def program_seconds(self, label: str) -> Tuple[float, int]:
        """Device time and runs of the executables under ``label`` (device
        0: the others run the same program)."""
        mods = [m for m in self.devices[0]["modules"]
                if self.module_label.get(m[0]) == label]
        return sum(m[2] for m in mods) / 1e9, len(mods)

    def kernel_events(self, kernel: str):
        """(result, operands, seconds, runs) of each distinct instruction
        that is the custom call the kernel was named as."""
        dev = self.devices[0]
        for i, (name, opcode, result, operands) in enumerate(dev["parsed"]):
            if name == kernel and opcode == "custom-call":
                yield (result, operands, float(dev["seconds"][i]),
                       int(dev["count"][i]))

    def top_ops(self, n: int = 10) -> List[List]:
        """The instructions that took most device time, by op and result
        shape; instructions that only contain others are left out."""
        dev = self.devices[0]
        sums: Dict[str, float] = defaultdict(float)
        for i, text in enumerate(dev["texts"]):
            if dev["parsed"][i][1] not in _CONTAINERS:
                sums[short_name(text)] += float(dev["seconds"][i])
        return [[k, v] for k, v in
                sorted(sums.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, phases: Iterable[str], n: int = 10) -> List[List]:
        """The idle time between module runs on device 0, summed by what the
        host was doing when each gap began: the benchmark's own phase span
        (one of ``phases``) and, inside it, the innermost host event of the
        runtime that covers that moment. Host and device clocks differ by
        about a millisecond, so gaps shorter than that land loosely."""
        import numpy as np

        mods = sorted(self.devices[0]["modules"], key=lambda m: m[1])
        if len(mods) < 2:
            return []
        phases = set(phases)
        names = [e[0] for e in self.host_events]
        start = np.asarray([e[1] for e in self.host_events], np.float64)
        end = start + np.asarray([e[2] for e in self.host_events], np.float64)
        sums: Dict[str, float] = defaultdict(float)
        for a, b in zip(mods, mods[1:]):
            t = a[1] + a[2]
            gap = (b[1] - t) / 1e9
            if gap <= 0:
                continue
            cover = np.flatnonzero((start <= t) & (t < end))
            outer = [i for i in cover if names[i] in phases]
            inner = [i for i in cover if names[i] not in phases]
            label = names[outer[0]] if outer else "outside"
            if inner:
                k = min(inner, key=lambda i: end[i] - start[i])
                label += "_" + re.sub(r"[^A-Za-z0-9]+", "_", names[k])
            sums[label] += gap
        return [[k, v] for k, v in
                sorted(sums.items(), key=lambda kv: -kv[1])[:n]]
